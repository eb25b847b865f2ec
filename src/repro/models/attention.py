"""Attention: blockwise-causal training/prefill, cached decode, compression.

Three execution paths:

* ``blockwise_attention`` — flash-style attention in pure ``lax.scan`` with
  online softmax; used for train/prefill lowering (the Pallas kernel in
  ``repro.kernels.flash`` is the TPU runtime twin, validated against the
  same reference).  Two schedules:
    - masked:   every (q-block, k-block) pair is computed and masked
                (2x FLOPs for causal — the naive baseline);
    - packed:   triangular block packing — only pairs with k <= q (and
                within the sliding window) are executed; exactly the
                useful FLOPs.  ``cfg.causal_block_skip`` selects it.
* ``decode_attention`` — one-token attention over a (possibly compressed)
  cache; bandwidth-bound, the paper's target.
* compressed variants — scores via (qB)(kA)^T, values via (p (vA)) C with
  C absorbing W^O (KQ-SVD factors from ``repro.core``).

All softmax statistics are f32 regardless of activation dtype.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels
from repro.config import AttnSpec, ModelConfig
from repro.kernels.kq_decode.kq_decode import kq_decode_attention
from repro.kernels.kq_decode.paged import (kq_decode_paged_attention,
                                           kq_window_paged_attention,
                                           kq_prefill_paged_attention)
from repro.models.layers import apply_rope, init_dense
from repro.serving.page_layouts import get_layout, quantize_int8  # noqa: F401
from repro.serving.paged_cache import (append_chunk, append_ring,
                                       append_token, gather_pages,
                                       gather_ring, layer_array,
                                       layer_set, ring_pages,
                                       ring_positions)
from repro.sharding import partition

NEG_INF = -1e30


def batched_positions(pos, batch: int) -> jnp.ndarray:
    """Normalize a decode position argument to (B,) int32.

    Scalars broadcast (the legacy lock-step contract); (B,) arrays pass
    through — every decode path downstream assumes per-sequence
    positions (DESIGN.md §decode)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (batch,))
    assert pos.shape == (batch,), (pos.shape, batch)
    return pos


def scatter_time(cache: jnp.ndarray, val: jnp.ndarray, slot: jnp.ndarray,
                 axis: int = 1) -> jnp.ndarray:
    """Write one new time-slot per sequence.

    cache: (B, ...), or a ``LayerPool`` of such (written in place in its
    stack); val: same with the time axis of size 1; slot: (B,)
    per-sequence destination index, clamped into the cache as a dynamic
    update slice clamps it; ``axis`` is the time axis *within a batch
    element* (1 for (B, Hkv, T, R) caches, 0 for (B, T, R))."""
    slot = jnp.clip(slot, 0, cache.shape[axis + 1] - 1)
    idx = (jnp.arange(slot.shape[0]),) + (slice(None),) * axis + (slot,)
    return layer_set(cache, idx, jnp.squeeze(val, axis + 1))


def int8_decode_attention(qg, k8, v8, kscale, vscale, valid, scale):
    """Dequantize-on-the-fly int8 decode: HBM reads stay int8.

    qg: (B, Hkv, m, R); k8/v8: (B, Hkv, T, R) int8; k/vscale: (B, Hkv, T);
    valid: (T,) or (B, T).  Returns (B, Hkv, m, R) group aggregates."""
    s = jnp.einsum("bgmr,bgtr->bgmt", qg.astype(jnp.float32),
                   k8.astype(jnp.float32)) * scale
    s = s * kscale.astype(jnp.float32)[:, :, None, :]
    vm = valid[None, None, None, :] if valid.ndim == 1 \
        else valid[:, None, None, :]
    s = jnp.where(vm, s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    pv = prob * vscale.astype(jnp.float32)[:, :, None, :]
    return jnp.einsum("bgmt,bgtr->bgmr", pv.astype(jnp.bfloat16),
                      v8.astype(jnp.bfloat16))


def int8_split_decode_attention(qg, k8, v8, kscale, vscale, valid, scale,
                                num_splits):
    """Split-KV twin of ``int8_decode_attention`` (DESIGN.md §split-kv).

    Same segment / partial-LSE / combine algebra as
    ``split_decode_attention``, but each segment runs the int8
    dot-then-scale math (scores from int8 keys scaled per token, value
    aggregation with the probability mass pre-multiplied by the value
    scales), so the paged int8 lax path covers ``decode_splits > 1``
    without a pallas kernel.  Shapes as in ``int8_decode_attention``."""
    B, Hkv, m, _ = qg.shape
    T = k8.shape[2]
    S = max(1, min(int(num_splits), T))
    seg = -(-T // S)
    S = -(-T // seg)
    s = jnp.einsum("bgmr,bgtr->bgmt", qg.astype(jnp.float32),
                   k8.astype(jnp.float32)) * scale
    s = s * kscale.astype(jnp.float32)[:, :, None, :]
    if valid.ndim == 1:
        vm = jnp.broadcast_to(valid[None, :], (B, T))
    else:
        vm = valid
    s = jnp.where(vm[:, None, None, :], s, NEG_INF)
    pad = S * seg - T
    s = jnp.pad(s, ((0, 0),) * 3 + ((0, pad),),
                constant_values=NEG_INF).reshape(B, Hkv, m, S, seg)
    vmp = jnp.pad(vm, ((0, 0), (0, pad))).reshape(B, 1, 1, S, seg)
    vs = jnp.pad(vscale.astype(jnp.float32), ((0, 0), (0, 0), (0, pad)))
    vs = vs.reshape(B, Hkv, 1, S, seg)
    v = jnp.pad(v8, ((0, 0), (0, 0), (0, pad), (0, 0)))
    v = v.reshape(B, Hkv, S, seg, -1).astype(jnp.bfloat16)
    mx = jnp.max(s, axis=-1)                                 # (B,Hkv,m,S)
    p = jnp.where(vmp, jnp.exp(s - mx[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    den = jnp.maximum(l, 1e-30)
    pv = (p * vs).astype(jnp.bfloat16)
    o = jnp.einsum("bgmst,bgstr->bgmsr", pv,
                   v).astype(jnp.float32) / den[..., None]
    lse = jnp.where(l > 0, mx + jnp.log(den), NEG_INF)       # (B,Hkv,m,S)
    m_star = jnp.max(lse, axis=-1, keepdims=True)
    w = jnp.exp(lse - m_star)
    num = jnp.sum(w[..., None] * o, axis=-2)                 # (B,Hkv,m,rv)
    agg = num / jnp.maximum(jnp.sum(w, axis=-1), 1e-30)[..., None]
    return agg.astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention in pure lax
# ---------------------------------------------------------------------------


def _gqa_expand(k: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """(B, Hkv, ...) -> (B, H, ...) by repeating each kv head m times."""
    m = n_heads // k.shape[1]
    if m == 1:
        return k
    return jnp.repeat(k, m, axis=1)


def _sink_denominator(l, m, sink):
    """Softmax denominator with a sink logit per query head (GPT-oss
    style: the sink takes probability mass and contributes no value).
    l, m: (B, H, ...) running sum and max; sink: (H,) or None."""
    if sink is None:
        return l
    sk = sink.astype(jnp.float32).reshape((1, -1) + (1,) * (m.ndim - 2))
    return l + jnp.exp(sk - m)


def reference_attention(q, k, v, *, causal=True, window=0,
                        scale: Optional[float] = None,
                        pos0_q: int = 0, sink=None):
    """O(S^2)-memory oracle (tests + tiny shapes). q:(B,H,S,dh);
    ``sink``: optional (H,) sink logits."""
    B, H, Sq, dh = q.shape
    Sk = k.shape[2]
    k = _gqa_expand(k, H)
    v = _gqa_expand(v, H)
    scale = scale or 1.0 / math.sqrt(dh)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = jnp.arange(Sq) + pos0_q
    kpos = jnp.arange(Sk)
    mask = kpos[None, :] <= qpos[:, None] if causal else jnp.ones(
        (Sq, Sk), bool)
    if window:
        mask = mask & (qpos[:, None] - kpos[None, :] < window)
    s = jnp.where(mask[None, None], s, NEG_INF)
    m = s.max(-1)
    e = jnp.exp(s - m[..., None])
    p = e / _sink_denominator(e.sum(-1), m, sink)[..., None]
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def blockwise_attention(q, k, v, *, causal=True, window=0,
                        block_q=512, block_k=512,
                        packed=True, scale=None, sink=None):
    """Flash-style blockwise attention.  q:(B,H,S,dh), k/v:(B,Hkv,S,dh).

    ``packed=True`` uses triangular block packing (causal only, requires
    block_q == block_k): the scan runs over exactly the lower-triangle
    (q-block, k-block) pairs so no masked-out block is ever computed.
    """
    B, H, S, dh = q.shape
    scale = scale or 1.0 / math.sqrt(dh)
    k = _gqa_expand(k, H)
    v = _gqa_expand(v, H)
    bq = min(block_q, S)
    bk = min(block_k, S)
    if S % bq or S % bk:
        return reference_attention(q, k, v, causal=causal, window=window,
                                   scale=scale, sink=sink)
    if packed and causal and bq == bk:
        return _packed_causal(q, k, v, bq, window, scale, sink)
    return _masked_blockwise(q, k, v, bq, bk, causal, window, scale, sink)


def _masked_blockwise(q, k, v, bq, bk, causal, window, scale, sink=None):
    B, H, S, dh = q.shape
    dv = v.shape[-1]
    Nq, Nk = S // bq, S // bk
    qb = q.reshape(B, H, Nq, bq, dh)
    kb = k.reshape(B, H, Nk, bk, dh)
    vb = v.reshape(B, H, Nk, bk, dv)

    def q_block(i):
        qi = qb[:, :, i]                                    # (B,H,bq,dh)
        qpos = i * bq + jnp.arange(bq)

        def kv_step(carry, j):
            m, l, acc = carry
            kj = jax.lax.dynamic_index_in_dim(kb, j, 2, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(vb, j, 2, keepdims=False)
            s = jnp.einsum("bhqd,bhkd->bhqk", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            kpos = j * bk + jnp.arange(bk)
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask = kpos[None, :] <= qpos[:, None]
            if window:
                mask = mask & (qpos[:, None] - kpos[None, :] < window)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vj.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((B, H, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        a0 = jnp.zeros((B, H, bq, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(Nk))
        l = _sink_denominator(l, m, sink)
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    out = jax.lax.map(q_block, jnp.arange(Nq))              # (Nq,B,H,bq,dv)
    return out.transpose(1, 2, 0, 3, 4).reshape(B, H, S, dv)


def _packed_causal(q, k, v, b, window, scale, sink=None):
    """Triangular block packing: scan over exactly the needed pairs."""
    B, H, S, dh = q.shape
    dv = v.shape[-1]
    N = S // b
    wblocks = N if not window else int(math.ceil(window / b))
    pairs = [(i, j) for i in range(N) for j in range(max(0, i - wblocks),
                                                     i + 1)]
    qi_arr = jnp.asarray(np.array([p[0] for p in pairs], np.int32))
    kj_arr = jnp.asarray(np.array([p[1] for p in pairs], np.int32))
    qb = q.reshape(B, H, N, b, dh)
    kb = k.reshape(B, H, N, b, dh)
    vb = v.reshape(B, H, N, b, dv)
    ar = jnp.arange(b)

    def step(carry, idx):
        m, l, acc = carry                                   # (B,H,N,b[,dh])
        i, j = idx
        qi = jax.lax.dynamic_index_in_dim(qb, i, 2, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(kb, j, 2, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vb, j, 2, keepdims=False)
        s = jnp.einsum("bhqd,bhkd->bhqk", qi, kj,
                       preferred_element_type=jnp.float32) * scale
        qpos = i * b + ar
        kpos = j * b + ar
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask = mask & (qpos[:, None] - kpos[None, :] < window)
        s = jnp.where(mask[None, None], s, NEG_INF)
        mi = jax.lax.dynamic_index_in_dim(m, i, 2, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, i, 2, keepdims=False)
        ai = jax.lax.dynamic_index_in_dim(acc, i, 2, keepdims=False)
        m_new = jnp.maximum(mi, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(mi - m_new)
        li = li * corr + p.sum(-1)
        ai = ai * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vj.astype(jnp.float32))
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, 2)
        l = jax.lax.dynamic_update_index_in_dim(l, li, i, 2)
        acc = jax.lax.dynamic_update_index_in_dim(acc, ai, i, 2)
        return (m, l, acc), None

    m0 = jnp.full((B, H, N, b), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, N, b), jnp.float32)
    a0 = jnp.zeros((B, H, N, b, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (qi_arr, kj_arr))
    l = _sink_denominator(l, m, sink)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype).reshape(B, H, S, dv)


# ---------------------------------------------------------------------------
# Decode attention over a cache (full or compressed)
# ---------------------------------------------------------------------------


def decode_attention(q, cache_k, cache_v, valid_mask, scale):
    """q: (B,H,1,dk); cache_k/v: (B,Hkv,T,*); valid_mask: (T,) or (B,T)."""
    B, H, _, dk = q.shape
    Hkv = cache_k.shape[1]
    m = H // Hkv
    qg = q.reshape(B, Hkv, m, dk)
    s = jnp.einsum("bgmd,bgtd->bgmt", qg, cache_k,
                   preferred_element_type=jnp.float32) * scale
    if valid_mask.ndim == 1:
        vm = valid_mask[None, None, None, :]
    else:
        vm = valid_mask[:, None, None, :]
    s = jnp.where(vm, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    agg = jnp.einsum("bgmt,bgtr->bgmr", p.astype(cache_v.dtype), cache_v)
    return agg                                              # (B,Hkv,m,rv)


def split_decode_attention(q, cache_k, cache_v, valid_mask, scale,
                           num_splits):
    """Split-KV twin of ``decode_attention`` (DESIGN.md §split-kv): the
    time axis is cut into ``num_splits`` contiguous segments, each
    segment contributes a partial (out, LSE) pair, and the pairs merge
    with the log-sum-exp rule — the same math as the Pallas split
    kernel's combine pass, in plain lax.  Exercised as the paged decode
    path whenever ``decode_splits > 1`` off TPU, so the whole serving
    suite covers the split+combine algebra on CPU.

    q: (B,H,1,dk); cache_k/v: (B,Hkv,T,*); valid_mask: (T,) or (B,T).
    Returns (B,Hkv,m,rv) like ``decode_attention``.
    """
    B, H, _, dk = q.shape
    Hkv, T = cache_k.shape[1], cache_k.shape[2]
    m = H // Hkv
    S = max(1, min(int(num_splits), T))
    seg = -(-T // S)
    S = -(-T // seg)
    qg = q.reshape(B, Hkv, m, dk)
    s = jnp.einsum("bgmd,bgtd->bgmt", qg, cache_k,
                   preferred_element_type=jnp.float32) * scale
    if valid_mask.ndim == 1:
        vm = jnp.broadcast_to(valid_mask[None, :], (B, T))
    else:
        vm = valid_mask
    s = jnp.where(vm[:, None, None, :], s, NEG_INF)
    pad = S * seg - T
    s = jnp.pad(s, ((0, 0),) * 3 + ((0, pad),),
                constant_values=NEG_INF).reshape(B, Hkv, m, S, seg)
    vmp = jnp.pad(vm, ((0, 0), (0, pad))).reshape(B, 1, 1, S, seg)
    v = jnp.pad(cache_v.astype(jnp.float32),
                ((0, 0), (0, 0), (0, pad), (0, 0)))
    v = v.reshape(B, Hkv, S, seg, -1)
    mx = jnp.max(s, axis=-1)                                 # (B,Hkv,m,S)
    p = jnp.where(vmp, jnp.exp(s - mx[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    den = jnp.maximum(l, 1e-30)
    o = jnp.einsum("bgmst,bgstr->bgmsr", p, v) / den[..., None]
    lse = jnp.where(l > 0, mx + jnp.log(den), NEG_INF)       # (B,Hkv,m,S)
    m_star = jnp.max(lse, axis=-1, keepdims=True)
    w = jnp.exp(lse - m_star)
    num = jnp.sum(w[..., None] * o, axis=-2)                 # (B,Hkv,m,rv)
    agg = num / jnp.maximum(jnp.sum(w, axis=-1), 1e-30)[..., None]
    return agg.astype(cache_v.dtype)


def chunk_decode_attention(qg, cache_k, cache_v, qpos, scale):
    """A chunk of S queries over a cache (lax reference for the paged
    prefill kernel).  qg: (B,Hkv,m,S,dk); cache_k/v: (B,Hkv,T,*);
    qpos: (B,S) per-query positions — query s of row b attends cache
    positions t <= qpos[b, s] (causal across *and within* the chunk,
    assuming the chunk's own entries are already written)."""
    T = cache_k.shape[2]
    s = jnp.einsum("bgmsd,bgtd->bgmst", qg, cache_k,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(T)[None, None, :] <= qpos[:, :, None]  # (B,S,T)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgmst,bgtr->bgmsr", p.astype(cache_v.dtype),
                      cache_v)                              # (B,Hkv,m,S,rv)


def window_attention(qg, keys, vals, kpos, kvalid, qpos, window: int,
                     scale: float, sink=None):
    """Queries over a window of keys whose positions are given (the lax
    path of the window rings, DESIGN.md §window-rings).

    qg: (B, Hkv, m, S, Rk) at positions ``qpos`` (B, S); keys/vals:
    (B, Hkv, T, R) at positions ``kpos`` (B, T), entries with ``kvalid``
    False ignored; query s attends keys with ``qpos - window < kpos <=
    qpos``.  ``sink``: optional (Hkv * m,) sink logits.  Returns
    (B, Hkv, m, S, Rv) f32."""
    B, Hkv, m = qg.shape[:3]
    s = jnp.einsum("bgmsr,bgtr->bgmst", qg.astype(jnp.float32),
                   keys.astype(jnp.float32)) * scale
    d = qpos[:, :, None] - kpos[:, None, :]                 # (B, S, T)
    ok = kvalid[:, None, :] & (d >= 0) & (d < window)
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    mx = s.max(-1)                                          # (B,Hkv,m,S)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(1, Hkv, m, 1)
        mx = jnp.maximum(mx, sk)
    e = jnp.exp(s - mx[..., None])
    den = e.sum(-1)
    if sink is not None:
        den = den + jnp.exp(sk - mx)
    out = jnp.einsum("bgmst,bgtr->bgmsr", e, vals.astype(jnp.float32))
    return out / jnp.maximum(den, 1e-30)[..., None]


def _split_table(cfg: ModelConfig, block_table, ps: int):
    """(context columns, ring columns) of a block table: the rows end in
    the window ring's columns when the model has a ringed kind."""
    w = cfg.ring_window
    if not w:
        return block_table, None
    n = ring_pages(w, ps)
    return block_table[:, :-n], block_table[:, -n:]


def _compress_kv(k, v, proj):
    """Keys and values of new tokens as the cache stores them."""
    if proj is None:
        return k, v
    return (jnp.einsum("bhtd,hdr->bhtr", k, proj["a_k"]),
            jnp.einsum("bhtd,hdr->bhtr", v, proj["a_v"]))


def _attn_out(p, agg, cfg: ModelConfig, Hkv: int, proj):
    """Group aggregates (B, Hkv, m_p, S, R) -> (B, S, D): through C_v
    with projections, else through W^O."""
    B, S = agg.shape[0], agg.shape[3]
    if proj is not None:
        m = cfg.n_heads // Hkv                  # real heads (c_v is real-m)
        c_v = proj["c_v"].reshape(Hkv, -1, m, cfg.d_model)
        return jnp.einsum("bgmsr,grmd->bsd", agg[:, :, :m], c_v)
    out = agg.reshape(B, padded_heads(cfg), S, agg.shape[-1])
    return jnp.einsum("bhse,hed->bsd", out, p["wo"])


def _window_step(p, x, cache, pos0, cfg: ModelConfig, spec: AttnSpec,
                 proj, ring_table, valid, decode: bool):
    """A windowed kind's prefill chunk or decode token over its per-slot
    page ring (DESIGN.md §window-rings).

    Prefill: the chunk's queries attend the ring as it stands (positions
    before ``pos0``) and the chunk's own new entries, so a chunk longer
    than the window attends across its boundary exactly; then the
    chunk's newest entries are written into the ring.  Decode: the
    token is written first, then attends the ring (on TPU the
    ``kq_window_paged_attention`` kernel, which reads at most
    ``ring_pages`` pages a slot)."""
    B, S, _ = x.shape
    Hkv, dh = spec.n_kv_heads, spec.d_qk
    m_p = padded_heads(cfg) // Hkv
    scale = 1.0 / math.sqrt(dh)
    positions = pos0[:, None] + jnp.arange(S)[None, :]       # (B, S)
    q, k_new, v_new = _qkv(p, x, cfg, positions[:, None, :], spec)
    kname, vname = ("kc", "vc") if proj is not None else ("k", "v")
    k_st, v_st = _compress_kv(k_new, v_new, proj)
    qg = q.reshape(B, Hkv, m_p, S, dh)
    if proj is not None:
        qg = jnp.einsum("bgmsd,gdr->bgmsr", qg, proj["b_q"])
    sink = p.get("sink")
    kp, vp = cache[kname], cache[vname]
    n = ring_table.shape[1] * kp.shape[2]                   # ring entries
    if decode:
        kp = append_ring(kp, ring_table, pos0, k_st, valid)
        vp = append_ring(vp, ring_table, pos0, v_st, valid)
        if kernels.use_kernels():
            agg = kq_window_paged_attention(
                qg.reshape(B, Hkv * m_p, -1), layer_array(kp),
                layer_array(vp), pos0 + 1,
                ring_table, window=spec.window, scale=scale,
                sink=sink).reshape(B, Hkv, m_p, 1, -1)
        else:
            kpos = ring_positions(pos0, n)
            agg = window_attention(qg, gather_ring(kp, ring_table),
                                   gather_ring(vp, ring_table), kpos,
                                   kpos >= 0, positions, spec.window,
                                   scale, sink)
    else:
        if valid.ndim == 1:
            valid = jnp.arange(S)[None, :] < valid[:, None]
        rpos = ring_positions(pos0 - 1, n)
        keys = jnp.concatenate([gather_ring(kp, ring_table),
                                k_st.astype(kp.dtype)], axis=2)
        vals = jnp.concatenate([gather_ring(vp, ring_table),
                                v_st.astype(vp.dtype)], axis=2)
        agg = window_attention(qg, keys, vals,
                               jnp.concatenate([rpos, positions], 1),
                               jnp.concatenate([rpos >= 0, valid], 1),
                               positions, spec.window, scale, sink)
        kp = append_ring(kp, ring_table, pos0, k_st, valid)
        vp = append_ring(vp, ring_table, pos0, v_st, valid)
    new_cache = dict(cache)
    new_cache[kname], new_cache[vname] = kp, vp
    y = _attn_out(p, agg.astype(x.dtype), cfg, Hkv, proj)
    return y.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# Attention layer (params + modes)
# ---------------------------------------------------------------------------


def padded_heads(cfg: ModelConfig) -> int:
    """Query-head count after TP padding (``qhead_pad`` or n_heads)."""
    return cfg.qhead_pad or cfg.n_heads


def head_mask(cfg: ModelConfig) -> Optional[jnp.ndarray]:
    """(Hp,) mask of real query heads under group-preserving padding.

    With qhead_pad, each kv group is padded from m to m_p query heads so
    the padded total divides the TP axis.  Pad heads have zero weights and
    their outputs are masked, so the function (and its gradients) equal
    the unpadded model exactly while every attention tensor shards.
    """
    Hp, H = padded_heads(cfg), cfg.n_heads
    if Hp == H:
        return None
    Hkv = cfg.n_kv_heads
    m, m_p = H // Hkv, Hp // Hkv
    mask = (jnp.arange(Hp) % m_p) < m
    return mask.astype(jnp.float32)


def init_attention(key, cfg: ModelConfig, dtype,
                   spec: Optional[AttnSpec] = None) -> Dict[str, jnp.ndarray]:
    """Init q/k/v/o projections (pad query heads zeroed, see
    ``head_mask``), and the per-head sink logits of a sink kind."""
    spec = spec or cfg.attn_spec()
    D, Hkv, dh, dv = cfg.d_model, spec.n_kv_heads, spec.d_qk, spec.d_v
    Hp = padded_heads(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": init_dense(k1, (D, Hp, dh), D, dtype),
        "wk": init_dense(k2, (D, Hkv, dh), D, dtype),
        "wv": init_dense(k3, (D, Hkv, dv), D, dtype),
        "wo": init_dense(k4, (Hp, dv, D), Hp * dv, dtype),
    }
    if spec.sink:
        p["sink"] = jnp.zeros((Hp,), jnp.float32)
    mask = head_mask(cfg)
    if mask is not None:
        p["wq"] = p["wq"] * mask[None, :, None].astype(dtype)
        p["wo"] = p["wo"] * mask[:, None, None].astype(dtype)
    return p


def _rope_part(x, positions, spec: AttnSpec):
    """RoPE on the leading ``spec.rotary_dim`` dims of x (all of them
    unless the kind rotates part of each head)."""
    r = spec.rotary_dim
    if r == x.shape[-1]:
        return apply_rope(x, positions, spec.rope_theta)
    return jnp.concatenate([apply_rope(x[..., :r], positions,
                                       spec.rope_theta), x[..., r:]], -1)


def _qkv(p, x, cfg: ModelConfig, positions, spec: Optional[AttnSpec] = None):
    """Project + rope.  x: (B,S,D) -> q (B,H,S,dqk), k (B,Hkv,S,dqk),
    v (B,Hkv,S,dv), the values times the kind's value scale."""
    spec = spec or cfg.attn_spec()
    q = jnp.einsum("bsd,dhe->bhse", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bhse", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bhse", x, p["wv"])
    if spec.value_scale != 1.0:
        v = v * jnp.asarray(spec.value_scale, v.dtype)
    q = _rope_part(q, positions, spec)
    k = _rope_part(k, positions, spec)
    return q, k, v


def attn_train(p, x, cfg: ModelConfig, pos0: int = 0,
               spec: Optional[AttnSpec] = None) -> jnp.ndarray:
    """Full-sequence causal attention (training / no-cache path)."""
    spec = spec or cfg.attn_spec()
    S = x.shape[1]
    positions = jnp.arange(S) + pos0
    q, k, v = _qkv(p, x, cfg, positions, spec)
    out = blockwise_attention(
        q, k, v, causal=True, window=spec.window,
        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
        packed=cfg.causal_block_skip, sink=p.get("sink"))
    mask = head_mask(cfg)
    if mask is not None:    # zero pad-head outputs => their grads stay 0
        out = out * mask[None, :, None, None].astype(out.dtype)
    return jnp.einsum("bhse,hed->bsd", out, p["wo"])


def attn_calibrate(p, x, cfg: ModelConfig,
                   spec: Optional[AttnSpec] = None
                   ) -> Tuple[jnp.ndarray, Dict]:
    """``attn_train`` plus captured q/k/v tensors for the KQ-SVD
    calibration pass (pad query heads excluded from the captures)."""
    spec = spec or cfg.attn_spec()
    S = x.shape[1]
    positions = jnp.arange(S)
    q, k, v = _qkv(p, x, cfg, positions, spec)
    out = blockwise_attention(
        q, k, v, causal=True, window=spec.window,
        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
        packed=cfg.causal_block_skip, sink=p.get("sink"))
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"])
    if padded_heads(cfg) != cfg.n_heads:     # drop pad heads from stats
        Hkv = cfg.n_kv_heads
        m = cfg.n_heads // Hkv
        m_p = padded_heads(cfg) // Hkv
        B_, _, S_, dh_ = q.shape
        q = q.reshape(B_, Hkv, m_p, S_, dh_)[:, :, :m].reshape(
            B_, cfg.n_heads, S_, dh_)
    captures = {"k": k, "q": q, "v": v}      # (B,Hkv,S,dh)/(B,H,S,dh)
    return y, captures


def group_output_weights(p, cfg: ModelConfig,
                         spec: Optional[AttnSpec] = None) -> np.ndarray:
    """W^O stacked per kv group: (Hkv, dv, m*D) for the value-path solve.

    Pad query heads (qhead_pad) are excluded: their weights are zero and
    their caches do not exist."""
    wo = np.asarray(p["wo"], np.float64)                     # (Hp, dv, D)
    Hp, dh, D = wo.shape
    Hkv = (spec or cfg.attn_spec()).n_kv_heads
    m = cfg.n_heads // Hkv
    m_p = Hp // Hkv
    wo = wo.reshape(Hkv, m_p, dh, D)[:, :m]
    return wo.transpose(0, 2, 1, 3).reshape(Hkv, dh, m * D)


def make_attn_cache(cfg: ModelConfig, batch: int, max_len: int,
                    proj_rank: Tuple[int, int] = (0, 0), dtype=jnp.bfloat16,
                    paged: bool = False, spec: Optional[AttnSpec] = None):
    """Empty cache pytree for one attention layer.

    ``paged=True`` reinterprets (batch, max_len) as (pages, page_size)
    and builds the pool leaves from the page layout ``cfg.cache_quant``
    selects (DESIGN.md §page-layouts): fp data pages for ``FpLayout``
    (bit-identical to the dense leaf shapes), int8/packed data pages
    plus width-1 bf16 scale pools for the quantized layouts."""
    spec = spec or cfg.attn_spec()
    # a paged window kind holds a ring of whole pages (DESIGN.md
    # §window-rings), never a per-slot window lane
    W = 0 if (paged and cfg.attn_kinds is not None) else spec.window
    T = min(max_len, W) if W else max_len
    Hkv = spec.n_kv_heads
    rk, rv = proj_rank
    if paged and rk:
        layout = get_layout(cfg)
        cache = {}
        for side, rank in (("k", rk), ("v", rv)):
            for name, width, ldt in layout.leaves(side, rank):
                cache[name] = jnp.zeros((batch, Hkv, T, width),
                                        ldt or dtype)
        return cache
    int8 = rk and cfg.cache_quant == "int8"
    if rk:
        cdt = jnp.int8 if int8 else dtype
        cache = {"kc": jnp.zeros((batch, Hkv, T, rk), cdt),
                 "vc": jnp.zeros((batch, Hkv, T, rv), cdt)}
        if int8:
            cache["kscale"] = jnp.zeros((batch, Hkv, T), jnp.bfloat16)
            cache["vscale"] = jnp.zeros((batch, Hkv, T), jnp.bfloat16)
    else:
        cache = {"k": jnp.zeros((batch, Hkv, T, spec.d_qk), dtype),
                 "v": jnp.zeros((batch, Hkv, T, spec.d_v), dtype)}
    if W:
        cache["slot_pos"] = jnp.full((batch, T), -1, jnp.int32)
    return cache


def attn_prefill(p, x, cfg: ModelConfig, max_len: int,
                 proj: Optional[Dict] = None):
    """Full-sequence attention; returns output and a length-max_len cache."""
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = _qkv(p, x, cfg, positions)
    out = blockwise_attention(
        q, k, v, causal=True, window=cfg.sliding_window,
        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
        packed=cfg.causal_block_skip)
    y = jnp.einsum("bhse,hed->bsd", out, p["wo"])
    cache = make_attn_cache(
        cfg, B, max_len,
        (proj["a_k"].shape[-1], proj["a_v"].shape[-1]) if proj else (0, 0),
        dtype=x.dtype)
    W = cfg.sliding_window or 0
    if W and S > W:
        k_st, v_st, kept = k[:, :, S - W:], v[:, :, S - W:], W
        kept_pos = jnp.arange(S - W, S)
    else:
        k_st, v_st, kept = k, v, S
        kept_pos = jnp.arange(S)
    if proj is not None:
        k_st = jnp.einsum("bhtd,hdr->bhtr", k_st, proj["a_k"])
        v_st = jnp.einsum("bhtd,hdr->bhtr", v_st, proj["a_v"])
        if cfg.cache_quant == "int8":
            k_st, ks = quantize_int8(k_st)
            v_st, vs = quantize_int8(v_st)
            updates = [("kc", k_st), ("vc", v_st), ("kscale", ks),
                       ("vscale", vs)]
        else:
            updates = [("kc", k_st), ("vc", v_st)]
    else:
        updates = [("k", k_st), ("v", v_st)]
    if W:
        slots = kept_pos % W
        for name, val in updates:
            cache[name] = cache[name].at[:, :, slots].set(
                val.astype(cache[name].dtype))
        cache["slot_pos"] = cache["slot_pos"].at[:, slots].set(kept_pos)
    else:
        for name, val in updates:
            cache[name] = jax.lax.dynamic_update_slice_in_dim(
                cache[name], val.astype(cache[name].dtype), 0, 2)
    return y, cache


def attn_prefill_chunk(p, x, cache: Dict, pos0, cfg: ModelConfig,
                       proj: Optional[Dict] = None, block_table=None,
                       valid=None, spec: Optional[AttnSpec] = None):
    """One bucket-padded prompt chunk straight into pages (DESIGN.md
    §prefill).

    x: (B, S, D) chunk whose first real token sits at position
    ``pos0[b]``; ``valid``: (B, S) marks real (non-bucket-padding)
    tokens, which must form a contiguous prefix — or a (B,) count of
    real tokens per row (the budget-truncated form, DESIGN.md
    §scheduler), forwarded as counts to ``append_chunk``.  The chunk's
    (compressed) k/v entries are written through ``block_table`` into
    the page pool — padding routes to the garbage page — and the
    chunk's queries attend the already-written pages (earlier chunks
    plus this one; causality via per-query positions).  Requires a
    paged cache; the exact-length ``attn_prefill`` + dense staging is
    the parity oracle.  Padded queries produce garbage rows: isolated
    (attention rows are independent, MoE masks them via ``valid``) and
    sliced away by the caller.  A windowed kind of a model with
    per-layer kinds (``spec``) runs over its page ring instead
    (``_window_step``).
    """
    if block_table is None:
        raise ValueError("attn_prefill_chunk requires a paged cache "
                         "(block_table)")
    if cfg.sliding_window:
        raise NotImplementedError(
            "chunked prefill supports full-attention stacks only "
            "(no sliding window)")
    spec = spec or cfg.attn_spec()
    B, S, _ = x.shape
    # slot-axis sharding constraint (DESIGN.md §sharded-engine): a
    # no-op without an active mesh — the sharded engine dispatches via
    # shard_map, where every shard already sees only its slice — but
    # under an active data mesh (pjit serving flows) it pins the
    # chunk's batch axis in place so GSPMD cannot gather it.
    x = partition.shard(x, ("pod", "data"), None, None)
    dh = spec.d_qk
    scale = 1.0 / math.sqrt(dh)
    pos0 = batched_positions(pos0, B)
    if valid is None:
        valid = jnp.ones((B, S), bool)
    ps = cache["kc" if proj is not None else "k"].shape[2]
    block_table, ring_table = _split_table(cfg, block_table, ps)
    if spec.window:
        with jax.named_scope("attn_window"):
            return _window_step(p, x, cache, pos0, cfg, spec, proj,
                                ring_table, valid, decode=False)
    if spec.sink:
        raise NotImplementedError("a sink on a full-attention kind is "
                                  "not served (the paged kernels have "
                                  "no sink)")
    with jax.named_scope("attn_full"):
        return _full_chunk(p, x, cache, pos0, cfg, spec, proj,
                           block_table, valid, scale)


def _full_chunk(p, x, cache, pos0, cfg, spec, proj, block_table, valid,
                scale):
    """``attn_prefill_chunk`` of a full-attention layer."""
    B, S, _ = x.shape
    dh = spec.d_qk
    # cache writes take either form; the count form stays counts so
    # the paged-store primitive exercises its own truncation contract
    wvalid = valid
    if valid.ndim == 1:
        valid = jnp.arange(S)[None, :] < valid[:, None]      # (B, S)
    positions = pos0[:, None] + jnp.arange(S)[None, :]       # (B, S)
    q, k_new, v_new = _qkv(p, x, cfg, positions[:, None, :], spec)
    T = block_table.shape[1] * cache[
        "kc" if proj is not None else "k"].shape[2]
    lengths = pos0 + valid.sum(axis=1).astype(jnp.int32)
    Hkv = spec.n_kv_heads
    Hp = padded_heads(cfg)
    m_p = Hp // Hkv
    qg = q.reshape(B, Hkv, m_p, S, dh)
    quant = False
    if proj is not None:
        k_st = jnp.einsum("bhtd,hdr->bhtr", k_new, proj["a_k"])
        v_st = jnp.einsum("bhtd,hdr->bhtr", v_new, proj["a_v"])
        layout = get_layout(cfg)
        quant = layout.kernel != "fp"
        if quant:
            # quantized page layout (DESIGN.md §page-layouts): encode
            # the chunk into data + scale leaves; every leaf writes
            # through the same block table and valid mask, so scale
            # pools stay in lockstep with their data pages
            enc = {**layout.encode("k", k_st), **layout.encode("v", v_st)}
            new_cache = dict(cache)
            for name, val in enc.items():
                new_cache[name] = append_chunk(cache[name], block_table,
                                               pos0, val, wvalid)
        else:
            kc = append_chunk(cache["kc"], block_table, pos0, k_st, wvalid)
            vc = append_chunk(cache["vc"], block_table, pos0, v_st, wvalid)
            new_cache = dict(cache, kc=kc, vc=vc)
        qg = jnp.einsum("bgmsd,gdr->bgmsr", qg, proj["b_q"])
    else:
        kc = append_chunk(cache["k"], block_table, pos0, k_new, wvalid)
        vc = append_chunk(cache["v"], block_table, pos0, v_new, wvalid)
        new_cache = dict(cache, k=kc, v=vc)
    if quant:
        # dequantize-then-attend lax twin: prefill is compute-bound
        # (the decode kernels carry the int8 HBM story), so chunks
        # gather + dequantize the written pages for every layout
        rk_ = proj["a_k"].shape[-1]
        rv_ = proj["a_v"].shape[-1]
        k_seq = layout.decode("k", {
            name: gather_pages(new_cache[name], block_table)
            for name, _, _ in layout.leaves("k", rk_)}, rk_)
        v_seq = layout.decode("v", {
            name: gather_pages(new_cache[name], block_table)
            for name, _, _ in layout.leaves("v", rv_)}, rv_)
        agg = chunk_decode_attention(qg, k_seq, v_seq, positions, scale)
    elif kernels.use_kernels():
        # TPU path: the prefill-append kernel streams the written pages
        # in place via the block table
        agg = kq_prefill_paged_attention(
            qg.reshape(B, Hp, S, -1), layer_array(kc), layer_array(vc),
            lengths, pos0,
            block_table, scale=scale,
            max_len=T).reshape(B, Hkv, m_p, S, -1)
    else:
        # lax path and the kernel's reference: materialize the slot's
        # pages, then the masked chunk attention
        agg = chunk_decode_attention(qg, gather_pages(kc, block_table),
                                     gather_pages(vc, block_table),
                                     positions, scale)
    y = _attn_out(p, agg, cfg, Hkv, proj)
    return y.astype(x.dtype), new_cache


def attn_decode(p, x, cache: Dict, pos, cfg: ModelConfig,
                proj: Optional[Dict] = None, block_table=None,
                num_splits: int = 1, spec: Optional[AttnSpec] = None):
    """One-token decode.  x: (B,1,D); pos: (B,) per-sequence index of the
    new token (a scalar broadcasts — legacy lock-step batches).

    ``block_table`` selects the paged cache (DESIGN.md §paged-cache):
    cache leaves are page pools (P, Hkv, page_size, R) and
    ``block_table`` is the (B, n_pages) slot->physical-page map; the new
    entry is appended through the table and attention reads the pages in
    place (Pallas) or via a gather (lax reference).  Dense (per-slot)
    caches remain the default and the parity oracle.

    ``num_splits`` (static, paged only) selects split-KV flash-decoding
    (DESIGN.md §split-kv): the Pallas path passes it to the paged
    kernel, the lax path routes through ``split_decode_attention``; 1
    is the unsplit parity oracle.  A windowed kind of a model with
    per-layer kinds (``spec``) decodes over its page ring
    (``_window_step``)."""
    B = x.shape[0]
    # slot-axis sharding constraint (DESIGN.md §sharded-engine): no-op
    # without an active mesh; under one it keeps the decode batch axis
    # device-local (no gathers on the hot path)
    x = partition.shard(x, ("pod", "data"), None, None)
    spec = spec or cfg.attn_spec()
    pos = batched_positions(pos, B)
    paged = block_table is not None
    if paged and cfg.attn_kinds is not None:
        ps = cache["kc" if proj is not None else "k"].shape[2]
        block_table, ring_table = _split_table(cfg, block_table, ps)
        if spec.window:
            with jax.named_scope("attn_window"):
                return _window_step(p, x, cache, pos, cfg, spec, proj,
                                    ring_table, jnp.ones((B, 1), bool),
                                    decode=True)
        if spec.sink:
            raise NotImplementedError("a sink on a full-attention kind "
                                      "is not served")
        with jax.named_scope("attn_full"):
            return _decode_core(p, x, cache, pos, cfg, proj, block_table,
                                num_splits, spec)
    return _decode_core(p, x, cache, pos, cfg, proj, block_table,
                        num_splits, spec)


def _decode_core(p, x, cache, pos, cfg, proj, block_table, num_splits,
                 spec: AttnSpec):
    """``attn_decode`` of a full-attention (or dense sliding-window)
    layer."""
    B = x.shape[0]
    dh = spec.d_qk
    scale = 1.0 / math.sqrt(dh)
    q, k_new, v_new = _qkv(p, x, cfg, pos[:, None, None], spec)   # S=1
    W = spec.window
    paged = block_table is not None
    layout = get_layout(cfg) if paged else None
    quant = paged and proj is not None and layout.kernel != "fp"
    if paged:
        if W:
            raise NotImplementedError(
                "paged cache supports full-attention stacks only "
                "(no sliding window)")
        T = block_table.shape[1] * cache[
            "kc" if proj is not None else "k"].shape[2]
    else:
        T = (cache["kc"] if proj is not None else cache["k"]).shape[2]
    slot = (pos % W) if W else pos                          # (B,)
    if proj is not None:
        k_st = jnp.einsum("bhtd,hdr->bhtr", k_new, proj["a_k"])
        v_st = jnp.einsum("bhtd,hdr->bhtr", v_new, proj["a_v"])
        int8 = cfg.cache_quant == "int8" and not paged
        if int8:
            k_st, ks_new = quantize_int8(k_st)
            v_st, vs_new = quantize_int8(v_st)
        if quant:
            # quantized page layout (DESIGN.md §page-layouts): encode
            # the token into data + scale leaves, each appended through
            # the same block table (scale pools move in lockstep)
            enc = {**layout.encode("k", k_st), **layout.encode("v", v_st)}
            new_cache = dict(cache)
            for name, val in enc.items():
                new_cache[name] = append_token(cache[name], block_table,
                                               pos, val[:, :, 0])
        elif paged:
            kc = append_token(cache["kc"], block_table, pos, k_st[:, :, 0])
            vc = append_token(cache["vc"], block_table, pos, v_st[:, :, 0])
            new_cache = dict(cache, kc=kc, vc=vc)
        else:
            kc = scatter_time(cache["kc"], k_st, slot)
            vc = scatter_time(cache["vc"], v_st, slot)
            new_cache = dict(cache, kc=kc, vc=vc)
        if int8:
            new_cache["kscale"] = scatter_time(
                cache["kscale"], ks_new.astype(jnp.bfloat16), slot)
            new_cache["vscale"] = scatter_time(
                cache["vscale"], vs_new.astype(jnp.bfloat16), slot)
        # compress query with the group's B factor
        Hkv = spec.n_kv_heads
        Hp = padded_heads(cfg)
        m_p = Hp // Hkv
        qg = q.reshape(B, Hkv, m_p, dh)
        qc = jnp.einsum("bgmd,gdr->bgmr", qg, proj["b_q"]).reshape(
            B, Hp, 1, -1)
        keys = layer_array(new_cache["kc"])
        vals = layer_array(new_cache["vc"])
        qq = qc
    else:
        if paged:
            kk = append_token(cache["k"], block_table, pos, k_new[:, :, 0])
            vv = append_token(cache["v"], block_table, pos, v_new[:, :, 0])
        else:
            kk = scatter_time(cache["k"], k_new, slot)
            vv = scatter_time(cache["v"], v_new, slot)
        new_cache = dict(cache, k=kk, v=vv)
        keys, vals = layer_array(kk), layer_array(vv)
        qq = q
    if W:
        new_cache["slot_pos"] = layer_set(cache["slot_pos"],
                                          (jnp.arange(B), slot), pos)
        slot_pos = layer_array(new_cache["slot_pos"])       # (B, T)
        valid = (slot_pos >= 0) & (slot_pos > pos[:, None] - W)
    else:
        valid = jnp.arange(T)[None, :] <= pos[:, None]      # (B, T)
    if proj is not None and cfg.cache_quant == "int8" and not paged:
        Hkv = spec.n_kv_heads
        m = padded_heads(cfg) // Hkv
        agg = int8_decode_attention(
            qq.reshape(B, Hkv, m, -1), keys, vals,
            layer_array(new_cache["kscale"]),
            layer_array(new_cache["vscale"]), valid, scale)
    elif quant and layout.kernel == "int8":
        # paged int8 (DESIGN.md §page-layouts): the pallas kernel
        # dequantizes on the fly from int8 pages + scale pools (unsplit
        # and split-KV variants); the lax twin runs the same
        # dot-then-scale math on gathered pages
        Hkv = spec.n_kv_heads
        if kernels.use_kernels():
            agg = kq_decode_paged_attention(
                qq.reshape(B, -1, qq.shape[-1]), keys, vals, pos + 1,
                block_table, scale=scale, max_len=T,
                num_splits=num_splits,
                kscale=layer_array(new_cache["kscale"]),
                vscale=layer_array(new_cache["vscale"])).reshape(
                    B, Hkv, -1, vals.shape[-1])
        else:
            m_p2 = padded_heads(cfg) // Hkv
            k8 = gather_pages(keys, block_table)
            v8 = gather_pages(vals, block_table)
            ks = gather_pages(new_cache["kscale"], block_table)[..., 0]
            vs = gather_pages(new_cache["vscale"], block_table)[..., 0]
            qg2 = qq.reshape(B, Hkv, m_p2, -1)
            if num_splits > 1:
                agg = int8_split_decode_attention(
                    qg2, k8, v8, ks, vs, valid, scale, num_splits)
            else:
                agg = int8_decode_attention(qg2, k8, v8, ks, vs, valid,
                                            scale)
    elif quant:
        # svdq is lax-only (layout.kernel is None): unpack + dequantize
        # the gathered pages, then the fp decode twins
        rk_ = proj["a_k"].shape[-1]
        rv_ = proj["a_v"].shape[-1]
        k_seq = layout.decode("k", {
            name: gather_pages(new_cache[name], block_table)
            for name, _, _ in layout.leaves("k", rk_)}, rk_)
        v_seq = layout.decode("v", {
            name: gather_pages(new_cache[name], block_table)
            for name, _, _ in layout.leaves("v", rv_)}, rv_)
        if num_splits > 1:
            agg = split_decode_attention(qq, k_seq, v_seq, valid, scale,
                                         num_splits)
        else:
            agg = decode_attention(qq, k_seq, v_seq, valid, scale)
    elif paged and kernels.use_kernels():
        # TPU path, paged: the kernel dereferences the block table via
        # scalar prefetch — no page gather is materialized
        Hkv = spec.n_kv_heads
        agg = kq_decode_paged_attention(
            qq.reshape(B, -1, qq.shape[-1]), keys, vals, pos + 1,
            block_table, scale=scale, max_len=T,
            num_splits=num_splits).reshape(B, Hkv, -1, vals.shape[-1])
    elif paged:
        # lax path and the paged kernel's reference: materialize each
        # slot's pages, then the dense masked decode; with
        # decode_splits > 1 the split twin runs the same partial-LSE
        # merge the split kernel uses
        k_seq = gather_pages(keys, block_table)
        v_seq = gather_pages(vals, block_table)
        if num_splits > 1:
            agg = split_decode_attention(qq, k_seq, v_seq, valid, scale,
                                         num_splits)
        else:
            agg = decode_attention(qq, k_seq, v_seq, valid, scale)
    elif kernels.use_kernels() and not W:
        # TPU path, dense: the Pallas kernel streams the cache with
        # per-sequence lengths
        Hkv = spec.n_kv_heads
        agg = kq_decode_attention(
            qq.reshape(B, -1, qq.shape[-1]), keys, vals, pos + 1,
            scale=scale, max_len=T).reshape(B, Hkv, -1, vals.shape[-1])
    else:
        agg = decode_attention(qq, keys, vals, valid, scale)  # (B,Hkv,m,rv)
    Hkv = spec.n_kv_heads
    y = _attn_out(p, agg.reshape(B, Hkv, -1, 1, agg.shape[-1]), cfg, Hkv,
                  proj)
    return y.astype(x.dtype), new_cache
