"""Decoder blocks and the scan-step grouping.

A *step* is the unit of ``lax.scan`` over depth: one layer for homogeneous
stacks, one hybrid period (e.g. Jamba's [ssm x4, attn, ssm x3]) for hybrid
stacks.  Every step in the scanned body has an identical pytree structure;
heterogeneous leading layers (DeepSeek-V2's first dense layer) live in an
unrolled prefix.

Layer pytree:
    {"ln1", "attn"|"ssm": {...}, ["ln2", "ffn": {...}]}
FFN is absent when d_ff == 0 and the layer is not MoE (pure Mamba-2).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import init_rms, init_swiglu, rms_norm, swiglu
from repro.models.moe import held_moe_ffn, init_moe, moe_ffn
from repro.serving.paged_cache import is_layer_pool, layer_array
from repro.sharding.partition import shard


# ---------------------------------------------------------------------------
# Step specification
# ---------------------------------------------------------------------------


def _signature(cfg: ModelConfig, layer_idx: int):
    """What makes two layers structurally identical (same pytree)."""
    kind = cfg.layer_kinds()[layer_idx]
    if cfg.attn_kinds is not None and kind == "attn":
        kind = ("attn", cfg.attn_kinds.pattern[layer_idx])
    return kind, cfg.ffn_kind(layer_idx)


def step_layout(cfg: ModelConfig) -> Tuple[List[int], List[List[int]]]:
    """(prefix_layer_ids, steps) where each step is a list of layer ids.

    The unrolled prefix (at least the ``moe.first_k_dense`` layers) and
    the period of the scanned steps are the shortest pair, fewest layers
    traced (prefix plus one period), whose steps are structurally
    identical: a plain stack scans every layer past its dense ones one
    at a time, a hybrid its repeating period, and MiMo's irregular first
    period (``F S S S S F``) runs unrolled before its ``S S S S S F``
    periods are scanned.  A body of one step runs unrolled as well: a
    scan over it buys nothing and would need its leaves stacked (the
    12-layer MiMo benchmark cut)."""
    first = (cfg.moe.first_k_dense
             if cfg.moe is not None and cfg.moe.first_k_dense else 0)
    n = cfg.n_layers
    sig = [_signature(cfg, i) for i in range(n)]
    best = None
    for k in range(first, n):
        body = n - k
        for p in range(1, body + 1):
            if body % p == 0 and all(sig[i] == sig[i + p]
                                     for i in range(k, n - p)):
                if best is None or k + p < sum(best):
                    best = (k, p)
                break
    k, period = best
    if n - k == period:
        k = n
    return list(range(k)), [list(range(i, i + period))
                            for i in range(k, n, period)]


def attn_sublayer_indices(cfg: ModelConfig, step: List[int]) -> List[int]:
    """Indices within the step of its attention-ish sublayers."""
    return [j for j, l in enumerate(step)
            if cfg.layer_kinds()[l] in ("attn", "mla")]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig, layer_idx: int, dtype) -> Dict:
    """Init one transformer layer's params for its configured kind
    (attn / mla / ssm sublayer plus dense or MoE FFN)."""
    kind = cfg.layer_kinds()[layer_idx]
    fk = cfg.ffn_kind(layer_idx)
    k1, k2, k3 = jax.random.split(key, 3)
    p: Dict[str, Any] = {"ln1": init_rms(cfg.d_model, dtype)}
    if kind == "attn":
        p["attn"] = attn_mod.init_attention(k1, cfg, dtype,
                                            cfg.attn_spec(layer_idx))
    elif kind == "mla":
        p["attn"] = mla_mod.init_mla(k1, cfg, dtype)
    else:
        p["ssm"] = ssm_mod.init_ssm(k1, cfg.d_model, cfg.ssm, dtype)
    has_ffn = (fk == "moe") or cfg.d_ff > 0
    if has_ffn:
        p["ln2"] = init_rms(cfg.d_model, dtype)
        if fk == "moe":
            p["ffn"] = init_moe(k2, cfg.d_model, cfg.moe, dtype,
                                cfg.routing)
        else:
            ff = cfg.d_ff
            if cfg.moe is not None and layer_idx < cfg.moe.first_k_dense:
                ff = cfg.moe.first_dense_ff or cfg.d_ff
            p["ffn"] = init_swiglu(k2, cfg.d_model, ff, dtype)
    return p


# ---------------------------------------------------------------------------
# Apply (one layer, all modes)
# ---------------------------------------------------------------------------


def _ffn_apply(p: Dict, x, cfg: ModelConfig, layer_idx: int, mode: str,
               token_mask=None):
    """Post-mixer FFN with residual; returns (x, aux)."""
    aux = {}
    if "ffn" not in p:
        return x, aux
    h = rms_norm(x, p["ln2"], cfg.rms_eps)
    if cfg.ffn_kind(layer_idx) == "moe" and cfg.routing is not None:
        y, aux = held_moe_ffn(p["ffn"], h, cfg.moe, cfg.routing,
                              token_mask=token_mask)
    elif cfg.ffn_kind(layer_idx) == "moe":
        y, aux = moe_ffn(p["ffn"], h, cfg.moe, mode,
                         token_mask=token_mask)
    else:
        y = swiglu(p["ffn"], h)
    return x + y, aux


def apply_layer(p: Dict, x, cfg: ModelConfig, layer_idx: int, mode: str,
                cache: Optional[Dict] = None, pos=None,
                proj: Optional[Dict] = None, max_len: int = 0,
                block_table=None, token_mask=None, num_splits: int = 1):
    """Returns (x, new_cache, captures, aux).

    ``block_table`` (decode only) routes attention through the paged
    cache; ``token_mask`` (B, S) marks live tokens so MoE routing skips
    finished/empty serving slots (both DESIGN.md §paged-cache).
    ``num_splits`` (decode only, static) selects split-KV
    flash-decoding in the paged attention path (DESIGN.md §split-kv)."""
    kind = cfg.layer_kinds()[layer_idx]
    x = shard(x, ("pod", "data"), None, None)
    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    new_cache, captures = None, None
    if (block_table is not None or mode == "chunk") and kind != "attn":
        raise NotImplementedError(
            f"paged cache / chunked prefill supports plain attention "
            f"layers only (got {kind})")
    if kind == "attn":
        spec = cfg.attn_spec(layer_idx)
        if (cfg.attn_kinds is not None and mode in ("prefill", "decode")
                and block_table is None):
            raise NotImplementedError(
                "per-layer attention kinds are served from the paged "
                "cache only (sinks and window rings have no dense path)")
        if mode == "train":
            y = attn_mod.attn_train(p["attn"], h, cfg, spec=spec)
        elif mode == "calibrate":
            y, captures = attn_mod.attn_calibrate(p["attn"], h, cfg, spec)
        elif mode == "prefill":
            y, new_cache = attn_mod.attn_prefill(p["attn"], h, cfg,
                                                 max_len, proj)
        elif mode == "chunk":
            y, new_cache = attn_mod.attn_prefill_chunk(
                p["attn"], h, cache, pos, cfg, proj, block_table,
                valid=token_mask, spec=spec)
        else:
            y, new_cache = attn_mod.attn_decode(p["attn"], h, cache, pos,
                                                cfg, proj, block_table,
                                                num_splits, spec=spec)
    elif kind == "mla":
        if mode == "train":
            y = mla_mod.mla_train(p["attn"], h, cfg)
        elif mode == "calibrate":
            y, captures = mla_mod.mla_calibrate(p["attn"], h, cfg)
        elif mode == "prefill":
            y, new_cache = mla_mod.mla_prefill(p["attn"], h, cfg,
                                               max_len, proj)
        else:
            y, new_cache = mla_mod.mla_decode(p["attn"], h, cache, pos,
                                              cfg, proj)
    else:  # ssm
        if mode in ("train", "calibrate"):
            y, _ = ssm_mod.ssm_forward(p["ssm"], h, cfg.ssm)
        elif mode == "prefill":
            y, new_cache = ssm_mod.ssm_forward(p["ssm"], h, cfg.ssm,
                                               return_state=True)
        else:
            # the state is rewritten whole: read the layer's, return a
            # new one for the step to store at the layer
            state = jax.tree.map(layer_array, cache, is_leaf=is_layer_pool)
            y, new_cache = ssm_mod.ssm_decode(p["ssm"], h, state, cfg.ssm)
    x = x + y
    ffn_mask = token_mask if mode in ("decode", "chunk") else None
    if ffn_mask is not None and mode == "chunk" and ffn_mask.ndim == 1:
        # budget-truncated count form (DESIGN.md §scheduler): the
        # attention path consumes counts natively, but MoE routing
        # needs the expanded per-token prefix mask
        ffn_mask = jnp.arange(x.shape[1])[None, :] < ffn_mask[:, None]
    x, aux = _ffn_apply(p, x, cfg, layer_idx, mode, ffn_mask)
    return x, new_cache, captures, aux


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def init_layer_cache(cfg: ModelConfig, layer_idx: int, batch: int,
                     max_len: int, ranks: Tuple[int, int], dtype,
                     paged: bool = False, ring_slots: int = 0):
    """Empty decode-cache pytree for one layer (``paged``: pool leaves
    built from the configured page layout; attention layers only).  A
    windowed layer of a model with per-layer kinds holds a ring of
    pages per slot instead (``ring_slots`` slots plus the garbage page;
    DESIGN.md §window-rings), whatever the context."""
    kind = cfg.layer_kinds()[layer_idx]
    if kind == "attn":
        spec = cfg.attn_spec(layer_idx)
        if paged and cfg.attn_kinds is not None and spec.window:
            batch = 1 + ring_slots * attn_mod.ring_pages(spec.window,
                                                         max_len)
        return attn_mod.make_attn_cache(cfg, batch, max_len, ranks, dtype,
                                        paged, spec)
    if kind == "mla":
        return mla_mod.make_mla_cache(cfg, batch, max_len, ranks, dtype)
    return ssm_mod.make_ssm_state(cfg.ssm, cfg.d_model, batch, dtype)
