"""Streaming calibration: accumulate Gram statistics, then solve projections.

The paper concatenates 128 x 2048-token caches into T=262,144-row matrices
and SVDs them.  We instead accumulate the d x d Gram matrices

    G_K = K^T K,   G_Q = sum_j Q_j^T Q_j (GQA group stack, Thm 5),
    G_V = V^T V

per (layer, kv-head) in float64 on host (f32 on device), which is exact for
every solver in ``projections.py`` and needs O(heads * d^2) memory instead
of O(T * d).  Under data parallelism the Grams are ``psum``-reducible.

Interface contract with the model zoo: ``model.apply(..., mode="calibrate")``
returns per-attention-layer captures ``{"k": (B,Hkv,T,dk), "q": (B,H,T,dk),
"v": (B,Hkv,T,dv)}`` (post-RoPE; MLA layers emit the latent as k/v with the
absorbed per-head queries — see DESIGN.md) and the model exposes the
per-group stacked output weights ``(Hkv, dv, Do_group)``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from repro.config import CompressionConfig
from repro.core.projections import (Factors, KeyProjection, ValueProjection,
                                    select_rank, solve_key, solve_value)


@dataclass
class LayerGrams:
    """Gram statistics for one attention layer (per kv head)."""

    g_k: np.ndarray            # (Hkv, dk, dk)
    g_q: np.ndarray            # (Hkv, dk, dk) — group-stacked queries
    g_v: np.ndarray            # (Hkv, dv, dv)
    tokens: int = 0


@dataclass
class ModelProjections:
    """Solved projections for every attention layer.

    Arrays are zero-padded to the layer-max rank so they stack cleanly for
    scan-over-layers execution; ``ranks_k``/``ranks_v`` record the true
    per-layer ranks (paper's per-layer selection).  Where attention
    layers differ in KV head count (per-layer kinds, MiMo-V2-Flash's 8
    and 4), each field is instead a list of per-layer arrays, in layer
    order: indexing by layer works the same either way.
    """

    a_k: Union[np.ndarray, List[np.ndarray]]   # (L_attn, Hkv, dk, R)
    b_q: Union[np.ndarray, List[np.ndarray]]   # (L_attn, Hkv, dk, R)
    a_v: Optional[Union[np.ndarray, List[np.ndarray]]]  # (L, Hkv, dv, Rv)
    c_v: Optional[Union[np.ndarray, List[np.ndarray]]]  # (L, Hkv, Rv, Do)
    ranks_k: List[int] = field(default_factory=list)
    ranks_v: List[int] = field(default_factory=list)
    method: str = "kqsvd"

    @property
    def rank_k(self) -> int:
        return self.a_k[0].shape[-1]

    @property
    def rank_v(self) -> int:
        return 0 if self.a_v is None else self.a_v[0].shape[-1]


class GramAccumulator:
    """Streaming Gram accumulation over calibration batches."""

    def __init__(self, n_layers: int):
        self.layers: List[Optional[LayerGrams]] = [None] * n_layers

    def update(self, ordinal: int, k: np.ndarray, q: np.ndarray,
               v: np.ndarray) -> None:
        """Accumulate one batch of captures for attention layer ``ordinal``.

        k: (B, Hkv, T, dk), q: (B, H, T, dk), v: (B, Hkv, T, dv).
        """
        k = np.asarray(k, np.float64)
        q = np.asarray(q, np.float64)
        v = np.asarray(v, np.float64)
        B, Hkv, T, dk = k.shape
        H = q.shape[1]
        m = H // Hkv
        dv = v.shape[-1]
        # group-stack queries: head j belongs to group j // m
        qg = q.reshape(B, Hkv, m, T, dk)
        g_k = np.einsum("bhtd,bhte->hde", k, k)
        g_q = np.einsum("bhmtd,bhmte->hde", qg, qg)
        g_v = np.einsum("bhtd,bhte->hde", v, v)
        st = self.layers[ordinal]
        if st is None:
            self.layers[ordinal] = LayerGrams(g_k, g_q, g_v, B * T)
        else:
            st.g_k += g_k
            st.g_q += g_q
            st.g_v += g_v
            st.tokens += B * T

    def update_from_captures(self, captures: Sequence[Dict]) -> None:
        for ordinal, cap in enumerate(captures):
            self.update(ordinal, cap["k"], cap["q"], cap["v"])

    # -- solving -----------------------------------------------------------

    def layer_factors(self, ordinal: int):
        st = self.layers[ordinal]
        assert st is not None, f"no statistics for layer {ordinal}"
        fk = [Factors.from_gram(g) for g in st.g_k]
        fq = [Factors.from_gram(g) for g in st.g_q]
        fv = [Factors.from_gram(g) for g in st.g_v]
        return fk, fq, fv

    def solve(self, cfg: CompressionConfig,
              w_out: Sequence[np.ndarray]) -> ModelProjections:
        """Solve projections for every layer with statistics.

        ``w_out[l]``: (Hkv, dv, Do_group) stacked output weights per layer.
        Rank: per-layer energy rule (paper) unless cfg.rank_{k,v} pins it;
        arrays are zero-padded to the max rank for shape uniformity.
        """
        assert cfg.method != "none"
        n = len(self.layers)
        key_projs: List[List[KeyProjection]] = []
        val_projs: List[List[ValueProjection]] = []
        ranks_k: List[int] = []
        ranks_v: List[int] = []
        for l in range(n):
            fk, fq, fv = self.layer_factors(l)
            rk = cfg.rank_k or select_rank(tuple(fk), cfg.epsilon)
            rv = cfg.rank_v or select_rank(tuple(fv), cfg.epsilon)
            ranks_k.append(rk)
            ranks_v.append(rv)
            key_projs.append([solve_key(cfg.method, fk[h], fq[h], rk)
                              for h in range(len(fk))])
            if cfg.compress_values:
                val_projs.append([solve_value(cfg.method, fv[h],
                                              w_out[l][h], rv)
                                  for h in range(len(fv))])
        Rk = max(ranks_k)
        a_k = _stack_pad([[p.A for p in layer] for layer in key_projs], Rk)
        b_q = _stack_pad([[p.B for p in layer] for layer in key_projs], Rk)
        a_v = c_v = None
        if cfg.compress_values:
            Rv = max(ranks_v)
            a_v = _stack_pad([[p.A for p in layer] for layer in val_projs],
                             Rv)
            c_v = _stack_pad_rows([[p.C for p in layer]
                                   for layer in val_projs], Rv)
        return ModelProjections(a_k=a_k, b_q=b_q, a_v=a_v, c_v=c_v,
                                ranks_k=ranks_k, ranks_v=ranks_v,
                                method=cfg.method)


def _stack(out: List[np.ndarray]):
    """One (L, ...) array, or the per-layer list where layers differ in
    shape (per-layer attention kinds)."""
    if len({a.shape for a in out}) == 1:
        return np.stack(out)
    return out


def _stack_pad(layers: List[List[np.ndarray]], R: int):
    """Stack (d, r_l) factors into (L, H, d, R), zero-padding columns."""
    out = []
    for layer in layers:
        heads = []
        for M in layer:
            pad = R - M.shape[1]
            heads.append(np.pad(M, ((0, 0), (0, pad))) if pad else M)
        out.append(np.stack(heads))
    return _stack(out)


def _stack_pad_rows(layers: List[List[np.ndarray]], R: int):
    """Stack (r_l, Do) factors into (L, H, R, Do), zero-padding rows."""
    out = []
    for layer in layers:
        heads = []
        for M in layer:
            pad = R - M.shape[0]
            heads.append(np.pad(M, ((0, pad), (0, 0))) if pad else M)
        out.append(np.stack(heads))
    return _stack(out)


# ---------------------------------------------------------------------------
# Distributed (pjit-able) calibration step
# ---------------------------------------------------------------------------


def make_calibrate_step(model):
    """Device-side Gram accumulation: a pure function suitable for pjit.

    ``calibrate_step(params, grams, tokens) -> grams`` where ``grams`` is
    {"g_k","g_q","g_v": (L_attn, Hkv, d, d) f32, "tokens": ()}.  Under a
    data-sharded batch GSPMD reduces the per-shard Gram contributions with
    a psum of O(L * H * d^2) bytes — independent of sequence length, which
    is what makes the paper's calibration phase run distributed at pod
    scale (DESIGN.md §4.1).  The host-side GramAccumulator path is the
    oracle (tests/test_calibration.py).
    """
    import jax.numpy as jnp

    def init_grams(dk: int, dv: int, hkv: int):
        L = len(model.attn_layers)
        return {
            "g_k": jnp.zeros((L, hkv, dk, dk), jnp.float32),
            "g_q": jnp.zeros((L, hkv, dk, dk), jnp.float32),
            "g_v": jnp.zeros((L, hkv, dv, dv), jnp.float32),
            "tokens": jnp.zeros((), jnp.float32),
        }

    def calibrate_step(params, grams, tokens):
        captures = model.calibrate(params, tokens)
        g_k, g_q, g_v = grams["g_k"], grams["g_q"], grams["g_v"]
        for ordinal, cap in enumerate(captures):
            k = cap["k"].astype(jnp.float32)
            q = cap["q"].astype(jnp.float32)
            v = cap["v"].astype(jnp.float32)
            B, Hkv, T, dk = k.shape
            m = q.shape[1] // Hkv
            qg = q.reshape(B, Hkv, m, T, dk)
            g_k = g_k.at[ordinal].add(
                jnp.einsum("bhtd,bhte->hde", k, k))
            g_q = g_q.at[ordinal].add(
                jnp.einsum("bhmtd,bhmte->hde", qg, qg))
            g_v = g_v.at[ordinal].add(
                jnp.einsum("bhtd,bhte->hde", v, v))
        B, T = tokens.shape[0], tokens.shape[-1]
        return {"g_k": g_k, "g_q": g_q, "g_v": g_v,
                "tokens": grams["tokens"] + B * T}

    return init_grams, calibrate_step


def accumulator_from_grams(grams) -> "GramAccumulator":
    """Adopt device-accumulated Grams into the host solver path."""
    import numpy as np_
    L = grams["g_k"].shape[0]
    acc = GramAccumulator(L)
    for l in range(L):
        acc.layers[l] = LayerGrams(
            g_k=np_.asarray(grams["g_k"][l], np_.float64),
            g_q=np_.asarray(grams["g_q"][l], np_.float64),
            g_v=np_.asarray(grams["g_v"][l], np_.float64),
            tokens=int(grams["tokens"]))
    return acc


# ---------------------------------------------------------------------------
# Driver: calibrate a model over a token stream
# ---------------------------------------------------------------------------


def calibrate_model(model, params, batches, cfg: CompressionConfig
                    ) -> ModelProjections:
    """Run calibration batches through ``model`` and solve projections.

    ``model`` follows the repro model protocol: ``model.calibrate(params,
    tokens)`` returns per-attention-layer captures, and
    ``model.group_output_weights(params)`` the stacked (Hkv, dv, Do_group)
    output weights per attention layer.
    """
    acc: Optional[GramAccumulator] = None
    for batch in batches:
        captures = model.calibrate(params, batch)
        if acc is None:
            acc = GramAccumulator(len(captures))
        acc.update_from_captures(captures)
    assert acc is not None, "no calibration batches supplied"
    w_out = model.group_output_weights(params)
    return acc.solve(cfg, w_out)
