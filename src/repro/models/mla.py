"""Multi-head Latent Attention (DeepSeek-V2) with latent-cache compression.

MLA stores a single trained low-rank latent c = x W_d (kv_lora dims) plus a
shared rotary key k_r per token.  At decode we use the absorbed form:

    score_h = (q_nope_h W_uk_h^T) c^T / sqrt(dqk)  +  q_rope_h k_r^T / sqrt(dqk)
    out     = sum_h p_h (c W_uv_h) W_o_h = sum_h (p_h c) W_vo_h

i.e. attention over the latent with per-head absorbed queries — exactly a
GQA structure with ONE kv head and H query heads, so Thm 5 applies and
KQ-SVD compresses the latent post-hoc (DESIGN.md §Arch-applicability):

    cc  = c A_k   (rank R  <  kv_lora)   for the score path,
    ccv = c A_v   (rank Rv <  kv_lora)   for the value path,
    absorbed query -> q'' = q' B_q;  output -> (p ccv) C_v.

The rope sub-cache (qk_rope_dim) is kept exact.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
import functools

from repro.models.attention import (NEG_INF, batched_positions,
                                    blockwise_attention, scatter_time)
from repro.models.layers import apply_rope, init_dense
from repro.serving.paged_cache import layer_array

# MLA caches are (B, T, R): the time axis within a batch element is 0
_scatter_seq = functools.partial(scatter_time, axis=0)


def init_mla(key, cfg: ModelConfig, dtype) -> Dict:
    """Init multi-head latent attention params (down/up projections,
    decoupled rope path, output projection)."""
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    keys = jax.random.split(key, 5)
    return {
        "wq": init_dense(keys[0], (D, H, qk), D, dtype),
        "wd": init_dense(keys[1], (D, m.kv_lora_rank + m.qk_rope_dim), D,
                         dtype),
        "wuk": init_dense(keys[2], (m.kv_lora_rank, H, m.qk_nope_dim),
                          m.kv_lora_rank, dtype),
        "wuv": init_dense(keys[3], (m.kv_lora_rank, H, m.v_head_dim),
                          m.kv_lora_rank, dtype),
        "wo": init_dense(keys[4], (H, m.v_head_dim, D), H * m.v_head_dim,
                         dtype),
    }


def _project(p, x, cfg: ModelConfig, positions):
    """Returns q_nope (B,H,S,nope), q_rope (B,H,S,rope), c (B,S,lora),
    k_rope (B,1,S,rope) — rope already applied."""
    m = cfg.mla
    q = jnp.einsum("bsd,dhe->bhse", x, p["wq"])
    q_nope = q[..., : m.qk_nope_dim]
    q_rope = apply_rope(q[..., m.qk_nope_dim:], positions, cfg.rope_theta)
    down = jnp.einsum("bsd,de->bse", x, p["wd"])
    c = down[..., : m.kv_lora_rank]
    k_rope = apply_rope(down[..., m.kv_lora_rank:][:, None],
                        positions, cfg.rope_theta)
    return q_nope, q_rope, c, k_rope


def mla_train(p, x, cfg: ModelConfig, pos0: int = 0) -> jnp.ndarray:
    """Full-sequence MLA via materialized per-head keys/values."""
    m = cfg.mla
    B, S, D = x.shape
    positions = jnp.arange(S) + pos0
    q_nope, q_rope, c, k_rope = _project(p, x, cfg, positions)
    k_nope = jnp.einsum("bsl,lhe->bhse", c, p["wuk"])
    v = jnp.einsum("bsl,lhe->bhse", c, p["wuv"])
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope, q_rope.shape[:1]
                                          + (cfg.n_heads,) + q_rope.shape[2:])
                         ], -1)
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    out = blockwise_attention(q, k, v, causal=True,
                              block_q=cfg.attn_block_q,
                              block_k=cfg.attn_block_k,
                              packed=cfg.causal_block_skip, scale=scale)
    return jnp.einsum("bhse,hed->bsd", out, p["wo"])


def mla_calibrate(p, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    """Captures for the latent-compression calibration (Hkv=1 GQA form)."""
    m = cfg.mla
    B, S, D = x.shape
    positions = jnp.arange(S)
    q_nope, q_rope, c, k_rope = _project(p, x, cfg, positions)
    y = mla_train(p, x, cfg)
    q_abs = jnp.einsum("bhse,lhe->bhsl", q_nope, p["wuk"])   # absorbed q'
    captures = {
        "k": c[:, None],                                     # (B,1,S,lora)
        "q": q_abs,                                          # (B,H,S,lora)
        "v": c[:, None],
    }
    return y, captures


def mla_group_output_weights(p, cfg: ModelConfig) -> np.ndarray:
    """Absorbed W_vo stacked over heads: (1, kv_lora, H*D)."""
    wuv = np.asarray(p["wuv"], np.float64)                   # (lora, H, dv)
    wo = np.asarray(p["wo"], np.float64)                     # (H, dv, D)
    w_vo = np.einsum("lhv,hvd->lhd", wuv, wo)                # (lora, H, D)
    lora = w_vo.shape[0]
    return w_vo.reshape(1, lora, -1)


def make_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   proj_rank: Tuple[int, int] = (0, 0), dtype=jnp.bfloat16):
    """Zeroed (B, T, R) MLA decode cache — compressed ``cc``/``ccv``
    leaves when KQ-SVD ranks are given, else the raw latent ``c``."""
    m = cfg.mla
    rk, rv = proj_rank
    if rk:
        cache = {"cc": jnp.zeros((batch, max_len, rk), dtype),
                 "ccv": jnp.zeros((batch, max_len, rv), dtype)}
    else:
        cache = {"c": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype)}
    cache["kr"] = jnp.zeros((batch, max_len, m.qk_rope_dim), dtype)
    return cache


def mla_prefill(p, x, cfg: ModelConfig, max_len: int,
                proj: Optional[Dict] = None):
    """Full-prompt MLA prefill: outputs plus a populated decode cache."""
    B, S, D = x.shape
    y = mla_train(p, x, cfg)
    positions = jnp.arange(S)
    _, _, c, k_rope = _project(p, x, cfg, positions)
    cache = make_mla_cache(
        cfg, B, max_len,
        (proj["a_k"].shape[-1], proj["a_v"].shape[-1]) if proj else (0, 0),
        dtype=x.dtype)
    if proj is not None:
        cc = jnp.einsum("bsl,lr->bsr", c, proj["a_k"][0])
        ccv = jnp.einsum("bsl,lr->bsr", c, proj["a_v"][0])
        cache["cc"] = jax.lax.dynamic_update_slice_in_dim(
            cache["cc"], cc.astype(cache["cc"].dtype), 0, 1)
        cache["ccv"] = jax.lax.dynamic_update_slice_in_dim(
            cache["ccv"], ccv.astype(cache["ccv"].dtype), 0, 1)
    else:
        cache["c"] = jax.lax.dynamic_update_slice_in_dim(
            cache["c"], c.astype(cache["c"].dtype), 0, 1)
    cache["kr"] = jax.lax.dynamic_update_slice_in_dim(
        cache["kr"], k_rope[:, 0].astype(cache["kr"].dtype), 0, 1)
    return y, cache


def mla_decode(p, x, cache: Dict, pos, cfg: ModelConfig,
               proj: Optional[Dict] = None):
    """One-token absorbed-form decode.  x: (B,1,D); pos: (B,) per-sequence
    index of the new token (a scalar broadcasts)."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    pos = batched_positions(pos, B)
    q_nope, q_rope, c_new, kr_new = _project(p, x, cfg, pos[:, None, None])
    q_abs = jnp.einsum("bhse,lhe->bhl", q_nope[:, :, :1], p["wuk"])
    kr_l = _scatter_seq(cache["kr"], kr_new[:, 0], pos)
    kr = layer_array(kr_l)
    T = kr.shape[1]
    valid = jnp.arange(T)[None, :] <= pos[:, None]           # (B, T)
    s_rope = jnp.einsum("bhse,bte->bht", q_rope, kr,
                        preferred_element_type=jnp.float32)
    if proj is not None:
        cc_new = jnp.einsum("bsl,lr->bsr", c_new, proj["a_k"][0])
        ccv_new = jnp.einsum("bsl,lr->bsr", c_new, proj["a_v"][0])
        new_cache = dict(cache, cc=_scatter_seq(cache["cc"], cc_new, pos),
                         ccv=_scatter_seq(cache["ccv"], ccv_new, pos),
                         kr=kr_l)
        cc, ccv = layer_array(new_cache["cc"]), layer_array(new_cache["ccv"])
        q_c = jnp.einsum("bhl,lr->bhr", q_abs, proj["b_q"][0])
        s_nope = jnp.einsum("bhr,btr->bht", q_c, cc,
                            preferred_element_type=jnp.float32)
        s = (s_nope + s_rope) * scale
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        prob = jax.nn.softmax(s, axis=-1)
        agg = jnp.einsum("bht,btr->bhr", prob.astype(ccv.dtype), ccv)
        c_v = proj["c_v"][0].reshape(-1, H, cfg.d_model)     # (Rv,H,D)
        y = jnp.einsum("bhr,rhd->bd", agg, c_v)[:, None]
    else:
        new_cache = dict(cache, c=_scatter_seq(cache["c"], c_new, pos),
                         kr=kr_l)
        cc = layer_array(new_cache["c"])
        s_nope = jnp.einsum("bhl,btl->bht", q_abs, cc,
                            preferred_element_type=jnp.float32)
        s = (s_nope + s_rope) * scale
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        prob = jax.nn.softmax(s, axis=-1)
        agg = jnp.einsum("bht,btl->bhl", prob.astype(cc.dtype), cc)
        v = jnp.einsum("bhl,lhe->bhe", agg, p["wuv"])
        y = jnp.einsum("bhe,hed->bd", v, p["wo"])[:, None]
    return y.astype(x.dtype), new_cache
