"""Compressed-cache ops (jnp) and size accounting.

The compressed cache stores, per attention layer and kv head,
``kc = K @ A_k`` (R dims) and ``vc = V @ A_v`` (Rv dims) instead of the
d-dimensional keys/values.  These helpers convert between representations
and account for bytes (used by the roofline analysis and the serving
engine's admission control).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.core.calibration import ModelProjections


def compress_kv(k: jnp.ndarray, v: jnp.ndarray,
                a_k: jnp.ndarray, a_v: jnp.ndarray):
    """Project a full cache into the compressed representation.

    k, v: (B, Hkv, T, d); a_k: (Hkv, d, R); a_v: (Hkv, d, Rv).
    """
    kc = jnp.einsum("bhtd,hdr->bhtr", k, a_k)
    vc = jnp.einsum("bhtd,hdr->bhtr", v, a_v)
    return kc, vc


def compress_queries(q: jnp.ndarray, b_q: jnp.ndarray) -> jnp.ndarray:
    """q: (B, H, T, d) -> (B, H, T, R) using the kv-group's B factor.

    b_q: (Hkv, d, R); query head j uses group j // (H // Hkv).
    """
    B, H, T, d = q.shape
    Hkv = b_q.shape[0]
    m = H // Hkv
    qg = q.reshape(B, Hkv, m, T, d)
    out = jnp.einsum("bgmtd,gdr->bgmtr", qg, b_q)
    return out.reshape(B, H, T, -1)


@dataclass(frozen=True)
class CacheFootprint:
    """Bytes per token per layer, full vs compressed."""

    full_bytes: int
    compressed_bytes: int

    @property
    def ratio(self) -> float:
        return self.compressed_bytes / max(1, self.full_bytes)


def cache_footprint(n_kv_heads: int, d_head: int, rank_k: int, rank_v: int,
                    itemsize: int = 2) -> CacheFootprint:
    full = n_kv_heads * 2 * d_head * itemsize
    comp = n_kv_heads * (rank_k + rank_v) * itemsize
    return CacheFootprint(full, comp)


def projection_param_bytes(p: ModelProjections, itemsize: int = 2) -> int:
    fields = [p.a_k, p.b_q] + ([p.a_v, p.c_v] if p.a_v is not None else [])
    # a field is one stacked array or a list of per-layer arrays
    return itemsize * sum(sum(np.size(a) for a in f) for f in fields)
