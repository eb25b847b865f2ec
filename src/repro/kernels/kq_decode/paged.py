"""Pallas TPU kernel: paged decode attention over the compressed cache.

Paged twin of ``kq_decode.kq_decode_attention`` (DESIGN.md
§paged-cache): kc/vc live in a page *pool* ``(P, Hkv, page_size, R)``
and each sequence's pages are located through a per-slot block table
``(B, n_pages)``.  Both the ``(B,)`` lengths and the block table enter
via scalar prefetch (SMEM), exactly the mechanism the variable-length
kernel already uses for lengths — the kc/vc BlockSpec index maps
dereference the block table to turn a *logical* time block into a
*physical* page id, so the kernel streams each sequence's pages from
HBM in place with no gather/copy:

* grid (B, Hkv / hb, Nt) with one time step per logical page,
  ``Nt = ceil(bound / page_size)`` where ``bound`` is the static
  ``max_len`` hint (never the allocated pool size);
* a program moves one page of ``hb`` KV heads: a pool page
  ``(Hkv, page_size, R)`` is contiguous, so ``hb`` heads of it are one
  DMA.  ``hb`` (``decode_heads_per_block``) is the largest divisor of
  Hkv whose double-buffered K/V blocks fit ``DECODE_VMEM_BUDGET`` —
  every KV head of the served models — since a program's cost is
  mostly fixed, not per byte;
* the index map clamps to the sequence's last occupied page, so
  programs past a short sequence re-reference the previous physical
  page and issue no fresh DMA;
* the online-softmax update (``_page_update``, shared by both decode
  variants) runs the ``hb`` heads as one batched matmul, is predicated
  with ``pl.when`` and masks ``tpos < length`` inside the tail page.

Layout: page_size is a sublane multiple (>=8) on real TPU; R_k/R_v are
lane-padded by the op wrapper (``ops.py``).

``kq_prefill_paged_attention`` is the prefill-append twin (DESIGN.md
§prefill): a whole bucket-padded chunk of S queries per sequence
attends the pages already written for it, with a per-query causal
position mask — chunked prefill streams the same pools the decode
kernel reads, no dense staging buffer.

``num_splits > 1`` selects the split-KV flash-decoding variant
(DESIGN.md §split-kv): the page chain is cut into ``num_splits``
contiguous spans, the grid gains a split axis — (B, Hkv / hb, S, span) —
and each split's program chain accumulates its own partial
(out, LSE) pair into per-split output blocks through the same
block-table index-map machinery.  ``combine_split_partials`` then
merges the splits with the numerically stable log-sum-exp rule.  A
32k-token sequence no longer serializes its whole chain through one
program: spans are independent along a parallelizable grid axis.

Both decode variants accept optional ``kscale``/``vscale``
(P, Hkv, ps, 1) pools (DESIGN.md §page-layouts): with them the kc/vc
pools hold int8 codes, the scale pools ride the identical block-table
index maps, and the kernels multiply the per-token amax scale back in
f32 after the int8 tiles land in VMEM — dequantize-on-the-fly, HBM
reads stay int8.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import LANE, default_interpret, pad_to_lane

NEG_INF = -1e30

# VMEM for one decode program's double-buffered K and V page blocks (and
# their scale blocks, with int8 pages).  Mosaic's default scoped VMEM
# limit on a v5e is 16 MiB; besides the blocks a program holds their f32
# widenings (as many bytes again for bf16 pages, twice as many for
# int8), the query, the scores and the f32 accumulator, so 4 MiB of
# blocks keeps a program near half that limit.  All KV heads of the
# served models fit one block (phi-3's 32 heads of 64x128 bf16 pages:
# 2 MiB double-buffered); a model with more folds them in groups.
DECODE_VMEM_BUDGET = 4 << 20
# Query rows (query heads of a group times chunk tokens) one prefill
# program holds.  A (8192, 128) query block makes Mosaic compile for
# about two minutes (v5e, described) against 24 s at 4096 rows, so a
# group with more rows than this runs as several calls, each over a
# slice of the group's query heads.
PREFILL_ROWS = 4096


def decode_heads_per_block(hkv: int, page_size: int, rk: int, rv: int,
                           itemsize: int, scale_itemsize: int = 0) -> int:
    """KV heads one decode program moves: the largest divisor of ``hkv``
    whose double-buffered K and V page blocks (and, with int8 pages,
    scale blocks of ``scale_itemsize`` bytes) fit
    ``DECODE_VMEM_BUDGET``.  Decided by the shapes alone."""
    # a (page_size, 1) scale block fills a whole 128-lane row per token
    row = (rk + rv) * itemsize + 2 * LANE * scale_itemsize
    per_head = 2 * page_size * row
    return max((d for d in range(1, hkv + 1)
                if hkv % d == 0 and d * per_head <= DECODE_VMEM_BUDGET),
               default=1)


def _unpack_refs(refs, quant: bool, n_out: int):
    """(k, kscale, v, vscale), outputs, scratch of a decode kernel's
    refs after the query; the scales are None for fp pages."""
    if quant:
        k_ref, ks_ref, v_ref, vs_ref, *rest = refs
    else:
        (k_ref, v_ref, *rest), ks_ref, vs_ref = refs, None, None
    return (k_ref, ks_ref, v_ref, vs_ref), rest[:n_out], rest[n_out:]


def _init_softmax(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _page_update(q_ref, pages, m_ref, l_ref, acc_ref, *, start, length,
                 scale: float, lo=None):
    """Online-softmax update of ``hb`` KV heads with one page whose first
    token sits at logical position ``start`` — the body both decode
    kernels share.

    Blocks: q (1, hb, m, Rk); k (1, hb, ps, Rk), v (1, hb, ps, Rv) and,
    for int8 pages, their (1, hb, ps, 1) scales.  Scratch: running max
    and sum (hb, m, 1) — keepdims layouts, so nothing is relaid out
    between the reductions and the broadcasts — and the f32 accumulator
    (hb, m, Rv).  Per head this is the single-head update: f32
    widening, f32 accumulation, ``tpos < length`` masking; with ``lo``
    (the window kernel) also ``tpos >= lo``.
    """
    k_ref, ks_ref, v_ref, vs_ref = pages
    q = q_ref[0].astype(jnp.float32)                      # (hb, m, Rk)
    k = k_ref[0].astype(jnp.float32)                      # (hb, ps, Rk)
    if ks_ref is not None:
        # dequantize in-register: HBM traffic stays int8 + one bf16
        # scale per token (DESIGN.md §page-layouts)
        k = k * ks_ref[0].astype(jnp.float32)             # (hb, ps, 1) bcast
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    tpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    live = tpos < length
    if lo is not None:
        live = live & (tpos >= lo)
    s = jnp.where(live, s, NEG_INF)                       # (hb, m, ps)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=2, keepdims=True)
    v = v_ref[0].astype(jnp.float32)                      # (hb, ps, Rv)
    if vs_ref is not None:
        v = v * vs_ref[0].astype(jnp.float32)             # (hb, ps, 1) bcast
    # zero the tail page's dead rows: 0 * garbage = NaN otherwise
    row = start + jax.lax.broadcasted_iota(jnp.int32, v.shape[:2] + (1,), 1)
    v = jnp.where(row < length, v, 0.0)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _kq_decode_paged_kernel(len_ref, btab_ref, q_ref, *refs, page_size: int,
                            scale: float, quant: bool):
    pages, (o_ref,), (m_ref, l_ref, acc_ref) = _unpack_refs(refs, quant, 1)
    b = pl.program_id(0)
    t = pl.program_id(2)
    length = len_ref[b]

    @pl.when(t == 0)
    def _init():
        _init_softmax(m_ref, l_ref, acc_ref)

    # Programs entirely past this sequence's last page are no-ops: the
    # block-table deref was clamped (no DMA) and the update is
    # predicated off.
    @pl.when(t * page_size < length)
    def _update():
        _page_update(q_ref, pages, m_ref, l_ref, acc_ref,
                     start=t * page_size, length=length, scale=scale)

    @pl.when(t == pl.num_programs(2) - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _kq_decode_paged_split_kernel(len_ref, btab_ref, q_ref, *refs,
                                  page_size: int, span: int, scale: float,
                                  quant: bool):
    pages, (o_ref, lse_ref), (m_ref, l_ref, acc_ref) = _unpack_refs(
        refs, quant, 2)
    b = pl.program_id(0)
    s = pl.program_id(2)
    t = pl.program_id(3)
    length = len_ref[b]
    # logical page of this program: page ``t`` of split ``s``'s span
    page = s * span + t

    @pl.when(t == 0)
    def _init():
        _init_softmax(m_ref, l_ref, acc_ref)

    # Programs past this sequence's last page — including every program
    # of a split whose whole span lies beyond it — are no-ops: the
    # block-table deref was clamped (no DMA) and the update is
    # predicated off, so the split emits an empty (0, -inf) partial.
    @pl.when(page * page_size < length)
    def _update():
        _page_update(q_ref, pages, m_ref, l_ref, acc_ref,
                     start=page * page_size, length=length, scale=scale)

    @pl.when(t == pl.num_programs(3) - 1)
    def _finish():
        # partial (out, LSE) pair for this split: out is the split's own
        # normalized softmax aggregate, lse = m + log(l) its partition
        # mass.  An empty split (l == 0) emits out = 0 and
        # lse ≈ NEG_INF + log(1e-30) — far enough below any live
        # split's lse that its combine weight underflows to exactly 0,
        # and equal across splits when *all* are empty (length 0), so
        # the merged output is 0 like the unsplit kernel's.
        denom = jnp.maximum(l_ref[...], 1e-30)            # (hb, m, 1)
        o_ref[0, :, 0] = acc_ref[...] / denom
        # lse is per query row; broadcast across the lane axis so the
        # output block keeps the (m, Rv) tile shape Mosaic expects —
        # the wrapper reads lane 0
        lse_ref[0, :, 0] = jnp.broadcast_to(m_ref[...] + jnp.log(denom),
                                            acc_ref.shape)


def combine_split_partials(o_parts, lse):
    """Merge per-split partial (out, LSE) pairs — the flash-decoding
    combine pass (DESIGN.md §split-kv).

    o_parts: (..., S, m, Rv) split-local softmax aggregates; lse:
    (..., S, m) split-local log-sum-exp (``m_s + log l_s``).  With
    ``lse* = max_s lse_s`` and weights ``w_s = exp(lse_s - lse*)``,
    the exact softmax over the concatenated splits is
    ``sum_s w_s out_s / sum_s w_s`` — subtracting the running max
    keeps every exponent <= 0, so the merge never overflows no matter
    how the score mass is distributed across splits.  Returns
    (..., m, Rv) in f32.
    """
    m_star = jnp.max(lse, axis=-2, keepdims=True)
    w = jnp.exp(lse - m_star)                            # (..., S, m)
    num = jnp.sum(w[..., None] * o_parts, axis=-3)
    den = jnp.maximum(jnp.sum(w, axis=-2), 1e-30)
    return num / den[..., None]


def _decode_in_specs(q_map, kv_map, hb, m, ps, Rk, Rv, quant):
    """BlockSpecs of a decode kernel's query, K (+ scale), V (+ scale):
    ``hb`` KV heads a block, the pages placed by ``kv_map``."""
    specs = [pl.BlockSpec((1, hb, m, Rk), q_map),
             pl.BlockSpec((1, hb, ps, Rk), kv_map)]
    if quant:
        specs.append(pl.BlockSpec((1, hb, ps, 1), kv_map))
    specs.append(pl.BlockSpec((1, hb, ps, Rv), kv_map))
    if quant:
        specs.append(pl.BlockSpec((1, hb, ps, 1), kv_map))
    return specs


def _softmax_scratch(hb, m, Rv):
    """VMEM scratch of ``_page_update``: running max and sum, then the
    f32 accumulator."""
    return [pltpu.VMEM((hb, m, 1), jnp.float32),
            pltpu.VMEM((hb, m, 1), jnp.float32),
            pltpu.VMEM((hb, m, Rv), jnp.float32)]


def _kq_decode_paged_split(qg, pools, lengths, block_table, *, scale: float,
                           interpret: bool, span: int, n_splits: int,
                           hb: int):
    """Launch the split-KV grid and merge the partials.

    qg: (B, Hkv, m, Rk) group-reshaped queries; ``pools`` the K (+ scale)
    and V (+ scale) pools in kernel order; spans/splits are resolved by
    the caller (``span * n_splits >= ceil(bound / ps)``, no empty
    trailing split).  Grid is (B, Hkv / hb, S, span); each (b, g, s)
    program chain walks pages ``s*span + t`` of the block table for
    ``hb`` KV heads and emits f32 partial blocks ``o_parts``
    (B, Hkv, S, m, Rv) and lane-broadcast ``lse_parts`` of the same
    shape, merged here by ``combine_split_partials``.  Returns
    (B, Hkv, m, Rv) in the query dtype.
    """
    B, Hkv, m, Rk = qg.shape
    ps = pools[0].shape[2]
    quant = len(pools) == 4
    Rv = pools[len(pools) // 2].shape[-1]

    def _kv_map(b, g, s, t, lens, btab):
        # same clamp-then-deref as the unsplit kernel, with the logical
        # page taken from this split's span; programs past the last
        # occupied page (or in a wholly-empty split) repeat a physical
        # page id and issue no fresh DMA
        last = jnp.maximum((lens[b] + ps - 1) // ps - 1, 0)
        return (btab[b, jnp.minimum(s * span + t, last)], g, 0, 0)

    def _out_map(b, g, s, t, lens, btab):
        return (b, g, s, 0, 0)

    kernel = functools.partial(_kq_decode_paged_split_kernel,
                               page_size=ps, span=span, scale=scale,
                               quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv // hb, n_splits, span),
        in_specs=_decode_in_specs(
            lambda b, g, s, t, lens, btab: (b, g, 0, 0), _kv_map,
            hb, m, ps, Rk, Rv, quant),
        out_specs=[pl.BlockSpec((1, hb, 1, m, Rv), _out_map)] * 2,
        scratch_shapes=_softmax_scratch(hb, m, Rv),
    )
    o_parts, lse_parts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, n_splits, m, Rv),
                                        jnp.float32)] * 2,
        interpret=interpret,
        name="kq_decode_paged_split",
    )(lengths, block_table, qg, *pools)
    out = combine_split_partials(o_parts, lse_parts[..., 0])
    return out.astype(qg.dtype)


def _kq_prefill_paged_kernel(len_ref, pos0_ref, btab_ref, q_ref, k_ref,
                             v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                             page_size: int, n_q: int, scale: float):
    b = pl.program_id(0)
    t = pl.program_id(2)
    nt = pl.num_programs(2)
    length = len_ref[b]
    p0 = pos0_ref[b]

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t * page_size < length)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)               # (m*S, Rk)
        k = k_ref[0, 0].astype(jnp.float32)               # (ps, Rk)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        tpos = t * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        # per-query causal: row r is query s = r % n_q of its head at
        # position p0 + s.  Pages ascend, so every row sees a valid key
        # in page 0 (tpos = 0 <= qpos) before any fully-masked page —
        # its running max is finite and masked exps underflow to 0.
        qpos = p0 + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) % n_q
        s = jnp.where((tpos <= qpos) & (tpos < length), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        v = v_ref[0, 0].astype(jnp.float32)               # (ps, Rv)
        # zero the tail page's dead rows: 0 * garbage = NaN otherwise
        row = t * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (v.shape[0], 1), 0)
        v = jnp.where(row < length, v, 0.0)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(t == nt - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def kq_prefill_paged_attention(qc, kc_pool, vc_pool, lengths, pos0,
                               block_table, *, scale: float = 1.0,
                               interpret: Optional[bool] = None,
                               max_len: Optional[int] = None,
                               pad_lanes: Optional[bool] = None):
    """Prefill-append entry: a chunk of S queries per sequence attends
    the pages already written for it (earlier chunks + its own, which
    the caller appends *before* the call — causality comes from the
    per-query position mask, DESIGN.md §prefill).

    qc: (B, H, S, Rk) chunk queries, query ``s`` of row ``b`` sits at
    position ``pos0[b] + s``; kc_pool/vc_pool: (P, Hkv, ps, R) page
    pools; ``lengths``: (B,) live cache entries (pos0 + valid chunk
    tokens); ``block_table``: (B, n_pages).  Same grid/prefetch
    mechanics as ``kq_decode_paged_attention`` — one time step per
    logical page, block-table deref in the index map, clamped past the
    last occupied page — with (m*S, ps) score tiles instead of (m, ps).
    Bucket-padded queries (``pos0 + s >= lengths``) fall back to a
    full-prefix mask: garbage rows, isolated and sliced by the caller.
    Budget-truncated chunks (DESIGN.md §scheduler: the token-budget
    scheduler cuts the last chunk of a step at the residual budget)
    need no kernel-side support — truncation only shrinks the valid
    prefix, so it reaches this entry as a smaller ``lengths`` under the
    same bucket shape and the padding mask covers the cut tail.

    Returns (B, H, S, Rv) group-aggregated values.
    """
    if interpret is None:
        interpret = default_interpret()
    if (not interpret) if pad_lanes is None else pad_lanes:
        rv = vc_pool.shape[-1]
        if qc.shape[-1] % 128 or rv % 128:
            out = kq_prefill_paged_attention(
                pad_to_lane(qc), pad_to_lane(kc_pool),
                pad_to_lane(vc_pool), lengths, pos0, block_table,
                scale=scale, interpret=interpret, max_len=max_len,
                pad_lanes=False)
            return out[..., :rv]
    B, H, S, Rk = qc.shape
    P, Hkv, ps, _ = kc_pool.shape
    Rv = vc_pool.shape[-1]
    m = H // Hkv
    n_calls = -(-m * S // PREFILL_ROWS)
    if n_calls > 1 and m % n_calls == 0:
        # each call takes m / n_calls query heads of every group
        sub = m // n_calls
        qs = qc.reshape(B, Hkv, m, S, Rk)
        outs = [kq_prefill_paged_attention(
            qs[:, :, i * sub:(i + 1) * sub].reshape(B, Hkv * sub, S, Rk),
            kc_pool, vc_pool, lengths, pos0, block_table, scale=scale,
            interpret=interpret, max_len=max_len, pad_lanes=False)
            for i in range(n_calls)]
        return jnp.concatenate(
            [o.reshape(B, Hkv, sub, S, Rv) for o in outs],
            axis=2).reshape(B, H, S, Rv)
    n_pages = block_table.shape[1]
    T = n_pages * ps
    lengths = jnp.asarray(lengths, jnp.int32)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths, (B,))
    pos0 = jnp.asarray(pos0, jnp.int32)
    if pos0.ndim == 0:
        pos0 = jnp.broadcast_to(pos0, (B,))
    block_table = jnp.asarray(block_table, jnp.int32)
    bound = T
    if max_len is not None:
        bound = max(1, min(T, int(max_len)))
    elif not isinstance(lengths, jax.core.Tracer):
        bound = max(1, min(T, int(jnp.max(lengths))))
    lengths = jnp.minimum(lengths, bound)
    grid = (B, Hkv, pl.cdiv(bound, ps))
    # rows ordered (m, S): row r is query r % S of head r // S
    qg = qc.reshape(B, Hkv, m * S, Rk)

    def _kv_map(b, g, t, lens, p0s, btab):
        last = jnp.maximum((lens[b] + ps - 1) // ps - 1, 0)
        return (btab[b, jnp.minimum(t, last)], g, 0, 0)

    kernel = functools.partial(_kq_prefill_paged_kernel, page_size=ps,
                               n_q=S, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, m * S, Rk),
                         lambda b, g, t, lens, p0s, btab: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, ps, Rk), _kv_map),
            pl.BlockSpec((1, 1, ps, Rv), _kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, m * S, Rv),
                               lambda b, g, t, lens, p0s, btab:
                               (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((m * S,), jnp.float32),
            pltpu.VMEM((m * S,), jnp.float32),
            pltpu.VMEM((m * S, Rv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, m * S, Rv), qc.dtype),
        interpret=interpret,
        name="kq_prefill_paged_attention",
    )(lengths, pos0, block_table, qg, kc_pool, vc_pool)
    return out.reshape(B, H, S, Rv)


def kq_decode_paged_attention(qc, kc_pool, vc_pool, lengths, block_table,
                              *, scale: float = 1.0,
                              interpret: Optional[bool] = None,
                              max_len: Optional[int] = None,
                              pad_lanes: Optional[bool] = None,
                              num_splits: int = 1,
                              kscale=None, vscale=None):
    """qc: (B,H,Rk); kc_pool: (P,Hkv,ps,Rk); vc_pool: (P,Hkv,ps,Rv).

    ``lengths``: (B,) int32 live cache entries per sequence;
    ``block_table``: (B, n_pages) int32 physical page of each logical
    page (unallocated entries may point anywhere valid — masked).
    ``max_len``: static bound on ``max(lengths)`` sizing the time grid
    under jit; same precondition as the dense kernel.  ``pad_lanes``
    (default: ``not interpret``) zero-pads non-lane-multiple R_k/R_v
    for Mosaic and slices the output back — exact (see
    ``kq_decode_attention``).

    ``num_splits > 1`` runs the split-KV flash-decoding variant
    (DESIGN.md §split-kv): the bounded page chain is cut into up to
    ``num_splits`` contiguous spans processed by independent program
    chains along a fourth grid axis, and their partial (out, LSE)
    pairs are merged by ``combine_split_partials``.  ``num_splits=1``
    (and any bound that fits one page) dispatches the single-program
    kernel unchanged — the bitwise parity oracle for the split path.

    ``kscale``/``vscale`` (both or neither) select the int8 page
    layout (DESIGN.md §page-layouts): kc/vc pools hold int8 codes and
    these (P, Hkv, ps, 1) pools hold the per-token bf16 amax scales,
    streamed through the same block-table index maps and multiplied
    back in-register after the int8 tiles land in VMEM — HBM reads
    stay int8.

    Returns (B, H, Rv) group-aggregated values.
    """
    if (kscale is None) != (vscale is None):
        raise ValueError("kscale/vscale must be passed together")
    quant = kscale is not None
    if interpret is None:
        interpret = default_interpret()
    if (not interpret) if pad_lanes is None else pad_lanes:
        rv = vc_pool.shape[-1]
        if qc.shape[-1] % 128 or rv % 128:
            # zero-padding the rank axis is exact for int8 codes too
            # (code 0 dequantizes to 0); the width-1 scale pools are
            # left alone — their lane axis is handled by the interpret
            # path, and on real TPU the scale tile would be widened at
            # the BlockSpec level instead (not exercised here).
            out = kq_decode_paged_attention(
                pad_to_lane(qc), pad_to_lane(kc_pool),
                pad_to_lane(vc_pool), lengths, block_table, scale=scale,
                interpret=interpret, max_len=max_len, pad_lanes=False,
                num_splits=num_splits, kscale=kscale, vscale=vscale)
            return out[..., :rv]
    B, H, Rk = qc.shape
    P, Hkv, ps, _ = kc_pool.shape
    Rv = vc_pool.shape[-1]
    m = H // Hkv
    n_pages = block_table.shape[1]
    T = n_pages * ps                        # logical capacity per slot
    lengths = jnp.asarray(lengths, jnp.int32)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths, (B,))
    block_table = jnp.asarray(block_table, jnp.int32)
    bound = T
    if max_len is not None:
        bound = max(1, min(T, int(max_len)))
    elif not isinstance(lengths, jax.core.Tracer):
        bound = max(1, min(T, int(jnp.max(lengths))))
    lengths = jnp.minimum(lengths, bound)
    nt = pl.cdiv(bound, ps)
    qg = qc.reshape(B, Hkv, m, Rk)
    # a split shorter than one page is an empty program chain: clamp,
    # then re-derive the split count from the span so no trailing
    # split starts past the bound (nt=8, num_splits=3 -> span 3,
    # splits 3; nt=4, num_splits=3 -> span 2, splits 2)
    n_splits = max(1, min(int(num_splits), nt))
    if n_splits > 1:
        span = pl.cdiv(nt, n_splits)
        n_splits = pl.cdiv(nt, span)
    pools = ([kc_pool, kscale, vc_pool, vscale] if quant
             else [kc_pool, vc_pool])
    hb = decode_heads_per_block(
        Hkv, ps, Rk, Rv, kc_pool.dtype.itemsize,
        kscale.dtype.itemsize if quant else 0)
    if n_splits > 1:
        return _kq_decode_paged_split(
            qg, pools, lengths, block_table, scale=scale,
            interpret=interpret, span=span, n_splits=n_splits,
            hb=hb).reshape(B, H, Rv)

    def _kv_map(b, g, t, lens, btab):
        # clamp to the last occupied logical page, then dereference the
        # block table: the physical page is the pipeline's block index,
        # so skipped programs repeat a page id and emit no fresh DMA
        last = jnp.maximum((lens[b] + ps - 1) // ps - 1, 0)
        return (btab[b, jnp.minimum(t, last)], g, 0, 0)

    def _q_map(b, g, t, lens, btab):
        return (b, g, 0, 0)

    kernel = functools.partial(_kq_decode_paged_kernel, page_size=ps,
                               scale=scale, quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv // hb, nt),
        in_specs=_decode_in_specs(_q_map, _kv_map, hb, m, ps, Rk, Rv,
                                  quant),
        out_specs=pl.BlockSpec((1, hb, m, Rv), _q_map),
        scratch_shapes=_softmax_scratch(hb, m, Rv),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, m, Rv), qc.dtype),
        interpret=interpret,
        name="kq_decode_paged_attention",
    )(lengths, block_table, qg, *pools)
    return out.reshape(B, H, Rv)


def _kq_window_paged_kernel(len_ref, rtab_ref, q_ref, sink_ref, *refs,
                            page_size: int, window: int, scale: float):
    pages, (o_ref,), (m_ref, l_ref, acc_ref) = _unpack_refs(refs, False, 1)
    b = pl.program_id(0)
    t = pl.program_id(2)
    length = len_ref[b]
    lo = jnp.maximum(length - window, 0)
    start = (lo // page_size + t) * page_size

    @pl.when(t == 0)
    def _init():
        _init_softmax(m_ref, l_ref, acc_ref)

    @pl.when(start < length)
    def _update():
        _page_update(q_ref, pages, m_ref, l_ref, acc_ref, start=start,
                     length=length, scale=scale, lo=lo)

    @pl.when(t == pl.num_programs(2) - 1)
    def _finish():
        # the sink takes probability mass and gives no value: rescale
        # to the larger of the running max and the sink, then add the
        # sink's exp to the sum
        sink = sink_ref[...].astype(jnp.float32)            # (hb, m, 1)
        m = m_ref[...]
        top = jnp.maximum(m, sink)
        c = jnp.exp(m - top)
        denom = jnp.maximum(l_ref[...] * c + jnp.exp(sink - top), 1e-30)
        o_ref[0] = (acc_ref[...] * c / denom).astype(o_ref.dtype)


def kq_window_paged_attention(qc, kc_pool, vc_pool, lengths, ring_table,
                              *, window: int, scale: float = 1.0,
                              sink=None, interpret: Optional[bool] = None,
                              pad_lanes: Optional[bool] = None):
    """Decode over a window kind's page rings (DESIGN.md §window-rings).

    qc: (B, H, Rk); kc_pool/vc_pool: (P, Hkv, ps, R) ring pools;
    ``lengths``: (B,) live positions (the newest token included);
    ``ring_table``: (B, ring) the slot's ring pages, logical page j in
    column ``j % ring``; ``sink``: optional (H,) sink logits.  The query
    attends positions ``[length - window, length)``.  Grid (B, Hkv / hb,
    ring): program t reads the window's t-th page, so a slot moves at
    most ``ring`` pages whatever its context; pages past the newest
    repeat its page id (no DMA) and skip the update.  Same online
    softmax as ``kq_decode_paged_attention`` (``_page_update`` with a
    lower bound), and the sink enters the final normalisation.
    Returns (B, H, Rv)."""
    if interpret is None:
        interpret = default_interpret()
    if (not interpret) if pad_lanes is None else pad_lanes:
        rv = vc_pool.shape[-1]
        if qc.shape[-1] % 128 or rv % 128:
            out = kq_window_paged_attention(
                pad_to_lane(qc), pad_to_lane(kc_pool),
                pad_to_lane(vc_pool), lengths, ring_table, window=window,
                scale=scale, sink=sink, interpret=interpret,
                pad_lanes=False)
            return out[..., :rv]
    B, H, Rk = qc.shape
    P, Hkv, ps, _ = kc_pool.shape
    Rv = vc_pool.shape[-1]
    m = H // Hkv
    ring = ring_table.shape[1]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    ring_table = jnp.asarray(ring_table, jnp.int32)
    if sink is None:
        sink = jnp.full((H,), NEG_INF, jnp.float32)
    sink = sink.astype(jnp.float32).reshape(Hkv, m, 1)
    hb = decode_heads_per_block(Hkv, ps, Rk, Rv, kc_pool.dtype.itemsize)

    def _kv_map(b, g, t, lens, rtab):
        # the window's t-th logical page, clamped to the newest, then
        # its ring column
        lo = jnp.maximum(lens[b] - window, 0) // ps
        last = jnp.maximum((lens[b] + ps - 1) // ps - 1, 0)
        return (rtab[b, jnp.minimum(lo + t, last) % ring], g, 0, 0)

    def _q_map(b, g, t, lens, rtab):
        return (b, g, 0, 0)

    kernel = functools.partial(_kq_window_paged_kernel, page_size=ps,
                               window=window, scale=scale)
    in_specs = _decode_in_specs(_q_map, _kv_map, hb, m, ps, Rk, Rv, False)
    in_specs.insert(1, pl.BlockSpec((hb, m, 1),
                                    lambda b, g, t, lens, rtab: (g, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv // hb, ring),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hb, m, Rv), _q_map),
        scratch_shapes=_softmax_scratch(hb, m, Rv),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, m, Rv), qc.dtype),
        interpret=interpret,
        name="kq_window_paged_attention",
    )(lengths, ring_table, qc.reshape(B, Hkv, m, Rk), sink, kc_pool,
      vc_pool)
    return out.reshape(B, H, Rv)

