"""Pallas TPU kernel: decode attention over the KQ-SVD-compressed cache.

This is the paper's runtime hot spot.  Per decoded token we stream the
compressed cache kc (T, R_k) / vc (T, R_v) HBM->VMEM in blocks of
``block_t`` and keep the online-softmax statistics for all m query heads
of a kv group in VREG/VMEM scratch.  The arithmetic intensity of decode
attention is ~1 FLOP/byte — pure bandwidth — so the kernel's job is to
touch every cache byte exactly once; the compression itself (R_k+R_v vs
2*d_head) is what moves the roofline (DESIGN.md §decode).

Variable-length batching (DESIGN.md §decode): every sequence in the
batch carries its own length.  The ``(B,)`` lengths array enters via
scalar prefetch (SMEM) and

* masks each (b, g) program against its own length (positions
  ``tpos < lengths[b]`` are live);
* clamps the kc/vc BlockSpec index maps to the sequence's last occupied
  block, so programs past a short sequence re-reference the previous
  block and the pipeline issues no new HBM traffic for them;
* predicates the whole online-softmax update with ``pl.when`` so those
  programs also do no compute.

The time grid itself is ``ceil(bound/block_t)`` where ``bound`` is the
static ``max_len`` hint (or ``max(lengths)`` when called with concrete
lengths outside jit) — the batch never pays for allocated cache slots
nobody occupies.  A non-divisible tail block (``T % block_t != 0``) is
handled by the same mask instead of an alignment assert.

Layout choices for TPU:
* R_k / R_v are zero-padded to lane multiples (128) by the caller;
* block_t is a sublane multiple (>=8; default 256);
* grid (B, Hkv, Nt), sequential in Nt so scratch persists per (b, g).

Output: per-group aggregated values (B, H, R_v); the C_v up-projection
(absorbing W^O) is a dense GEMM left outside the kernel where the MXU
handles it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret, pad_to_lane

NEG_INF = -1e30


def _kq_decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                      m_ref, l_ref, acc_ref, *, block_t: int,
                      scale: float):
    b = pl.program_id(0)
    t = pl.program_id(2)
    nt = pl.num_programs(2)
    length = len_ref[b]

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Programs entirely past this sequence's length are no-ops: their
    # block indices were clamped (no DMA) and the update is predicated.
    @pl.when(t * block_t < length)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)               # (m, Rk)
        k = k_ref[0, 0].astype(jnp.float32)               # (bt, Rk)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        tpos = t * block_t + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(tpos < length, s, NEG_INF)          # (m, bt)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        v = v_ref[0, 0].astype(jnp.float32)               # (bt, Rv)
        # zero padded tail rows: p there is 0, but 0 * NaN-pad = NaN
        row = t * block_t + jax.lax.broadcasted_iota(
            jnp.int32, (v.shape[0], 1), 0)
        v = jnp.where(row < length, v, 0.0)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(t == nt - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0, :, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


def kq_decode_attention(qc, kc, vc, lengths, *, block_t: int = 256,
                        scale: float = 1.0,
                        interpret: Optional[bool] = None,
                        max_len: Optional[int] = None,
                        pad_lanes: Optional[bool] = None):
    """qc: (B,H,Rk); kc: (B,Hkv,T,Rk); vc: (B,Hkv,T,Rv).

    ``lengths``: (B,) int32 count of live cache entries per sequence
    (positions ``0..lengths[b]-1`` attend); a scalar broadcasts to the
    batch.  ``max_len``: optional static upper bound on ``max(lengths)``
    used to size the time grid under jit (where lengths is traced); when
    lengths is concrete the bound is taken from the data.  PRECONDITION:
    ``max_len >= max(lengths)`` when given — lengths are clamped to the
    bound (traced values cannot be checked here), so an underestimated
    hint silently drops the tail of longer sequences.

    Lane padding (arbitrary calibrated ranks on real TPU): Mosaic needs
    the trailing axis to be a 128-multiple, so when compiling the real
    kernel (``pad_lanes`` defaults to ``not interpret``) R_k/R_v are
    zero-padded and the output sliced back — exact, since padded R_k
    columns add 0 to every score and padded R_v columns are dropped.

    Returns (B, H, Rv) group-aggregated values (softmax(qc kc^T) vc).
    """
    if interpret is None:
        interpret = default_interpret()
    if (not interpret) if pad_lanes is None else pad_lanes:
        rv = vc.shape[-1]
        if qc.shape[-1] % 128 or rv % 128:
            out = kq_decode_attention(
                pad_to_lane(qc), pad_to_lane(kc), pad_to_lane(vc),
                lengths, block_t=block_t, scale=scale,
                interpret=interpret, max_len=max_len, pad_lanes=False)
            return out[..., :rv]
    B, H, Rk = qc.shape
    _, Hkv, T, _ = kc.shape
    Rv = vc.shape[-1]
    m = H // Hkv
    bt = min(block_t, T)
    lengths = jnp.asarray(lengths, jnp.int32)
    if lengths.ndim == 0:
        lengths = jnp.broadcast_to(lengths, (B,))
    bound = T
    if max_len is not None:
        bound = max(1, min(T, int(max_len)))
    elif not isinstance(lengths, jax.core.Tracer):
        bound = max(1, min(T, int(jnp.max(lengths))))
    lengths = jnp.minimum(lengths, bound)
    grid = (B, Hkv, pl.cdiv(bound, bt))
    qg = qc.reshape(B, Hkv, m, Rk)

    def _kv_map(b, g, t, lens):
        # clamp to the sequence's last occupied block: repeated block
        # indices emit no fresh DMA for skipped programs
        last = jnp.maximum((lens[b] + bt - 1) // bt - 1, 0)
        return (b, g, jnp.minimum(t, last), 0)

    kernel = functools.partial(_kq_decode_kernel, block_t=bt, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, m, Rk), lambda b, g, t, lens: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, bt, Rk), _kv_map),
            pl.BlockSpec((1, 1, bt, Rv), _kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, m, Rv),
                               lambda b, g, t, lens: (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((m,), jnp.float32),
            pltpu.VMEM((m,), jnp.float32),
            pltpu.VMEM((m, Rv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, m, Rv), qc.dtype),
        interpret=interpret,
        name="kq_decode_attention",
    )(lengths, qg, kc, vc)
    return out.reshape(B, H, Rv)
