"""Decode-step cost: full vs KQ-SVD-compressed cache, fixed vs
variable-length.

Wall time on this CPU container is not the scored metric (TPU is the
target); the derived columns are the cache bytes/token, the analytic HBM
traffic of each variant (computed from the *actual* cache dtype widths —
2 bytes for bf16, 1 byte for the int8 path plus its scales) and the
measured step-latency ratios.  The ``decode_varlen_*`` rows drive the
lengths-aware kernel at several occupancy levels of the same allocated
cache: the time grid is bounded by the actual max length, so the cost of
a decode step tracks ``max(lengths)``, not ``max_seq_len``
(DESIGN.md §decode).  The ``decode_ttft_*`` / ``decode_mixed_step``
rows price chunked page-direct prefill against the dense-staging
oracle and the piggybacked prefill+decode step (DESIGN.md §prefill);
``decode_fused_step`` re-runs the mixed step's exact work as a single
jitted dispatch — the token-budget scheduler's fused iteration
(DESIGN.md §scheduler) — so its quotient against ``decode_mixed_step``
gates the launch-overhead saving of fusing.
The ``decode_paged_int8`` / ``decode_paged_svdq`` rows price the same
full-occupancy paged decode on quantized page layouts
(DESIGN.md §page-layouts): int8 scale-pool pages through the
dequantize-on-the-fly kernel and SVDq per-rank-bit packed pages
through the lax unpack twin — their hbm_bytes scale with the packed
page stride and the ``resident_x`` field is the extra resident
sequences the same pool holds.
The ``decode_longctx`` / ``decode_longctx_split`` rows price one
long page chain decoded through a single program chain vs the
split-KV flash-decoding variant (partial (out, LSE) spans merged by a
log-sum-exp combine, DESIGN.md §split-kv).
The ``decode_reserve`` / ``decode_preempt_*`` rows are an *engine*
scenario: the same oversubscribed request batch (total pool pages <
sum of the requests' worst cases) served end-to-end under reserve
admission on an ample pool vs optimistic admission with
preempt-and-recompute / preempt-and-swap on a small one
(DESIGN.md §preemption).  The ``decode_shared_prefix`` row serves a
common-system-prompt batch through the refcounted prefix-sharing
store (DESIGN.md §prefix-sharing), recording prefill-chunk and
pool-occupancy savings against the same batch unshared.
The ``decode_sharded_*`` rows drain the same batch through the
data-axis sharded engine (DESIGN.md §sharded-engine) on a forced
4-host-device CPU mesh in a subprocess (the bench process must keep
the single real device): per-slot step cost at 1, 2 and 4 shards,
with pooled capacity and per-shard peak occupancy in the derived
fields — the quotients vs the 1-shard drain (and vs the paged decode
kernel) gate hot-path gathers sneaking into the sharded dispatch.
All these quotients feed the machine-normalized regression gate
(``check_regression.RATIO_PAIRS``).
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, timed
from repro.core.compressed import cache_footprint
from repro.kernels.kq_decode import (default_decode_splits,
                                     kq_decode_attention_op,
                                     kq_decode_paged_attention_op,
                                     kq_prefill_paged_attention_op)
from repro.models.attention import (decode_attention,
                                    int8_decode_attention, quantize_int8)
from repro.serving.page_layouts import Int8Layout, SvdqLayout
from repro.serving.paged_cache import (append_chunk, gather_pages,
                                       pages_needed)


def _hbm_bytes(*arrays) -> int:
    """Analytic HBM traffic of one decode step: every cache byte read
    once, at its real dtype width."""
    return int(sum(a.size * a.dtype.itemsize for a in arrays))


def run(B: int = 4, Hkv: int = 8, m: int = 8, T: int = 4096,
        d: int = 128, R: int = 64, quick: bool = False) -> List[Row]:
    if quick:
        B, Hkv, m, T, d, R = 2, 2, 2, 512, 64, 32
    H = Hkv * m
    dt = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q_full = jax.random.normal(ks[0], (B, H, 1, d), dt)
    k_full = jax.random.normal(ks[1], (B, Hkv, T, d), dt)
    v_full = jax.random.normal(ks[2], (B, Hkv, T, d), dt)
    valid = jnp.ones((T,), bool)
    scale = 0.1

    fn_full = jax.jit(lambda q, k, v: decode_attention(q, k, v, valid,
                                                       scale))
    _, us_full = timed(fn_full, q_full, k_full, v_full)

    q_c = q_full[..., :R]
    k_c = k_full[..., :R]
    v_c = v_full[..., :R]
    _, us_comp = timed(fn_full, q_c, k_c, v_c)

    k8, kscale = quantize_int8(k_c)
    v8, vscale = quantize_int8(v_c)
    qg8 = q_c.reshape(B, Hkv, m, R)
    fn_int8 = jax.jit(lambda q, k, v, ksc, vsc: int8_decode_attention(
        q, k, v, ksc, vsc, valid, scale))
    _, us_int8 = timed(fn_int8, qg8, k8, v8, kscale, vscale)

    fp = cache_footprint(Hkv, d, R, R)
    hbm_full = _hbm_bytes(k_full, v_full)
    hbm_comp = _hbm_bytes(k_c, v_c)
    hbm_int8 = _hbm_bytes(k8, v8, kscale, vscale)
    print("\n== decode_costs: full vs compressed decode attention ==")
    print(f"T={T} d={d} R={R}: lax step {us_full:.0f}us -> {us_comp:.0f}us"
          f" ({us_full/us_comp:.2f}x), int8 {us_int8:.0f}us; hbm/step "
          f"{hbm_full} -> {hbm_comp} -> {hbm_int8} B")
    rows: List[Row] = [
        ("decode_full_cache", us_full,
         f"hbm_bytes={hbm_full};bytes_per_tok={fp.full_bytes}"),
        ("decode_kqsvd_cache", us_comp,
         f"hbm_bytes={hbm_comp};bytes_per_tok={fp.compressed_bytes}"),
        ("decode_kqsvd_int8", us_int8,
         f"hbm_bytes={hbm_int8};bytes_per_tok="
         f"{hbm_int8 // (B * T)}"),
        ("decode_speedup", us_full / us_comp,
         f"cache_reduction={1/fp.ratio:.3f}x"),
    ]

    # -- variable-length decode: cost tracks actual max length, not the
    # allocated max_seq_len (the kernel's time grid is ceil(L/bt)).
    # Small (B, Hkv) slice: interpret-mode grids are walked per program
    # on CPU, and the scaling story lives in the time grid, not the size.
    bt = 128 if quick else 256
    Bv, Gv = min(B, 2), min(Hkv, 4)
    qc2 = jax.random.normal(ks[3], (Bv, Gv * m, R), dt)
    k_v, v_v = k_c[:Bv, :Gv], v_c[:Bv, :Gv]
    for frac, tag in ((1.0, "full"), (0.5, "half"), (0.125, "eighth")):
        L = max(bt, int(T * frac))
        lens = jnp.linspace(L // 2, L, Bv).astype(jnp.int32)
        _, us = timed(kq_decode_attention_op, qc2, k_v, v_v, lens,
                      reps=5, block_t=bt, scale=scale, max_len=L)
        grid_nt = -(-L // bt)
        touched = int(np.sum(np.ceil(np.asarray(lens) / bt))) * bt \
            * Gv * 2 * R * k_c.dtype.itemsize
        rows.append((f"decode_varlen_{tag}", us,
                     f"max_len={L};grid_nt={grid_nt};alloc_T={T};"
                     f"hbm_bytes={touched}"))
        print(f"varlen[{tag}]: max_len={L} grid_nt={grid_nt} "
              f"{us:.0f}us hbm={touched}B")

    # -- paged cache: HBM scales with *occupied pages*, not with the
    # dense allocation slots x max_seq_len (DESIGN.md §paged-cache).
    # The pool holds full capacity; each occupancy level owns only the
    # pages its lengths need, located through a shuffled block table.
    ps = 64 if quick else 256
    pages_per_seq = T // ps
    n_phys = 1 + Bv * pages_per_seq                  # + garbage page 0
    kp = jax.random.normal(ks[1], (n_phys, Gv, ps, R), dt)
    vp = jax.random.normal(ks[2], (n_phys, Gv, ps, R), dt)
    page_bytes = Gv * ps * 2 * R * kp.dtype.itemsize
    dense_hbm = Bv * T * Gv * 2 * R * kp.dtype.itemsize
    perm = np.random.default_rng(0).permutation(
        np.arange(1, n_phys, dtype=np.int32))
    lens_full = btab_full = None
    for frac, tag in ((1.0, "full"), (0.5, "half"), (0.125, "eighth")):
        L = max(ps, int(T * frac))
        lens = jnp.linspace(L // 2, L, Bv).astype(jnp.int32)
        occupied = int(sum(pages_needed(int(x), ps)
                           for x in np.asarray(lens)))
        btab = np.zeros((Bv, pages_per_seq), np.int32)
        nxt = 0
        for b, x in enumerate(np.asarray(lens)):
            n_b = pages_needed(int(x), ps)
            btab[b, :n_b] = perm[nxt: nxt + n_b]
            nxt += n_b
        if tag == "full":
            lens_full, btab_full = lens, jnp.asarray(btab)
        _, us = timed(kq_decode_paged_attention_op, qc2, kp, vp, lens,
                      jnp.asarray(btab), reps=5, scale=scale, max_len=L)
        rows.append((f"decode_paged_{tag}", us,
                     f"max_len={L};page_size={ps};"
                     f"occupied_pages={occupied};"
                     f"alloc_pages={Bv * pages_per_seq};"
                     f"hbm_bytes={occupied * page_bytes};"
                     f"dense_hbm_bytes={dense_hbm}"))
        print(f"paged[{tag}]: max_len={L} pages={occupied}/"
              f"{Bv * pages_per_seq} {us:.0f}us "
              f"hbm={occupied * page_bytes}B (dense {dense_hbm}B)")

    # -- quantized page layouts (DESIGN.md §page-layouts): the same
    # full-occupancy decode on int8 scale-pool pages (the pallas kernel
    # dequantizes on the fly — HBM reads stay int8) and on SVDq
    # per-rank-bit packed pages (lax-only: unpack + dequantize the
    # gathered pages, then the fp decode twin).  Each row's hbm_bytes
    # scale with the *packed* page stride; the derived ``resident_x``
    # quotient (fp page bytes / packed page bytes) is how many more
    # resident sequences the same physical pool holds at that layout.
    occ_full = int(sum(pages_needed(int(x), ps)
                       for x in np.asarray(lens_full)))
    kp8, kps = quantize_int8(kp)
    vp8, vps = quantize_int8(vp)
    kps = kps[..., None].astype(jnp.bfloat16)            # (P,Gv,ps,1)
    vps = vps[..., None].astype(jnp.bfloat16)
    _, us_p8 = timed(kq_decode_paged_attention_op, qc2, kp8, vp8,
                     lens_full, btab_full, reps=5, scale=scale,
                     max_len=T, kscale=kps, vscale=vps)
    int8_page = Gv * ps * sum(Int8Layout().token_bytes(s, R)
                              for s in ("k", "v"))
    rows.append(("decode_paged_int8", us_p8,
                 f"max_len={T};page_size={ps};"
                 f"occupied_pages={occ_full};"
                 f"page_bytes={int8_page};fp_page_bytes={page_bytes};"
                 f"hbm_bytes={occ_full * int8_page};"
                 f"resident_x={page_bytes / int8_page:.2f}"))
    sv = SvdqLayout()
    enc_k = sv.encode("k", kp)
    enc_v = sv.encode("v", vp)
    q_sv = qc2[:, :, None, :]                            # (Bv,H,1,R)
    valid_sv = jnp.arange(T)[None, :] < lens_full[:, None]

    @jax.jit
    def svdq_step(kc_, ksc_, vc_, vsc_):
        k_seq = sv.decode("k", {
            "kc": gather_pages(kc_, btab_full),
            "kscale": gather_pages(ksc_, btab_full)}, R)
        v_seq = sv.decode("v", {
            "vc": gather_pages(vc_, btab_full),
            "vscale": gather_pages(vsc_, btab_full)}, R)
        return decode_attention(q_sv, k_seq, v_seq, valid_sv, scale)

    _, us_sv = timed(svdq_step, enc_k["kc"], enc_k["kscale"],
                     enc_v["vc"], enc_v["vscale"], reps=5)
    sv_page = Gv * ps * sum(sv.token_bytes(s, R) for s in ("k", "v"))
    sv_bits = sv.resolve_bits(R)
    rows.append(("decode_paged_svdq", us_sv,
                 f"max_len={T};page_size={ps};"
                 f"occupied_pages={occ_full};"
                 f"bits_hi={sv_bits[0]};bits_lo={sv_bits[-1]};"
                 f"page_bytes={sv_page};fp_page_bytes={page_bytes};"
                 f"hbm_bytes={occ_full * sv_page};"
                 f"resident_x={page_bytes / sv_page:.2f}"))
    print(f"paged layouts: int8 {us_p8:.0f}us "
          f"(page {int8_page}B, x{page_bytes / int8_page:.2f} resident) "
          f"svdq {us_sv:.0f}us "
          f"(page {sv_page}B, x{page_bytes / sv_page:.2f} resident)")

    # -- split-KV flash-decoding at long context (DESIGN.md §split-kv):
    # ONE slot owning every pool page — the scenario where the unsplit
    # kernel serializes the whole chain through a single program chain
    # while the rest of the grid idles.  The split variant cuts the
    # chain into ``default_decode_splits`` spans along a parallel grid
    # axis; the ``decode_longctx_split/decode_longctx`` quotient gates
    # it (<= 1.0x; the win is grid parallelism on real TPU — in CPU
    # interpret mode the program count is equal, so the quotient sits
    # near 1).
    n_long = Bv * pages_per_seq
    L_long = n_long * ps
    btab_l = jnp.asarray(perm[:n_long][None, :])
    lens_l = jnp.asarray([L_long], jnp.int32)
    q_l = qc2[:1]
    n_split = default_decode_splits(L_long, ps)
    span = -(-n_long // n_split)
    _, us_long = timed(kq_decode_paged_attention_op, q_l, kp, vp,
                       lens_l, btab_l, reps=5, scale=scale,
                       max_len=L_long)
    _, us_split = timed(kq_decode_paged_attention_op, q_l, kp, vp,
                        lens_l, btab_l, reps=5, scale=scale,
                        max_len=L_long, num_splits=n_split)
    rows.append(("decode_longctx", us_long,
                 f"length={L_long};pages={n_long};page_size={ps};"
                 f"num_splits=1"))
    rows.append(("decode_longctx_split", us_split,
                 f"length={L_long};pages={n_long};page_size={ps};"
                 f"num_splits={n_split};span_pages={span}"))
    print(f"longctx: L={L_long} pages={n_long} unsplit {us_long:.0f}us "
          f"vs split[{n_split}] {us_split:.0f}us "
          f"({us_long/us_split:.2f}x)")

    # -- chunked prefill into pages (DESIGN.md §prefill): time-to-first-
    # token through bucket-compiled chunk writes vs the exact-length
    # dense-staging oracle, whose (1, alloc_T) buffer is the worst-case
    # HBM spike the chunked path removes; plus the sarathi-style mixed
    # step that piggybacks one prefill chunk on a decode iteration.
    C = 2 * ps
    Lp = T // 2
    n_chunks = Lp // C
    n_prompt_pages = Lp // ps
    btab1 = jnp.asarray(perm[:pages_per_seq][None, :])       # one slot
    kq = jax.random.split(jax.random.PRNGKey(7), 3)
    q_ch = jax.random.normal(kq[0], (n_chunks, 1, Gv * m, C, R), dt)
    k_ch = jax.random.normal(kq[1], (n_chunks, 1, Gv, C, R), dt)
    v_ch = jax.random.normal(kq[2], (n_chunks, 1, Gv, C, R), dt)
    kp0 = jnp.zeros_like(kp)
    vp0 = jnp.zeros_like(vp)
    append_j = jax.jit(append_chunk)
    valid1 = jnp.ones((1, C), bool)

    def prefill_chunk_call(i, kpool, vpool):
        pos0 = jnp.asarray([i * C], jnp.int32)
        kpool = append_j(kpool, btab1, pos0, k_ch[i], valid1)
        vpool = append_j(vpool, btab1, pos0, v_ch[i], valid1)
        out = kq_prefill_paged_attention_op(
            q_ch[i], kpool, vpool, jnp.asarray([(i + 1) * C], jnp.int32),
            pos0, btab1, scale=scale, max_len=Lp)
        return out, kpool, vpool

    def ttft_chunked():      # one compile per bucket, reused every chunk
        kpool, vpool, out = kp0, vp0, None
        for i in range(n_chunks):
            out, kpool, vpool = prefill_chunk_call(i, kpool, vpool)
        return out

    q_all = jnp.concatenate(list(q_ch), axis=2)              # (1,H,Lp,R)
    k_all = jnp.concatenate(list(k_ch), axis=2)
    v_all = jnp.concatenate(list(v_ch), axis=2)
    phys1 = btab1[0, :n_prompt_pages]

    @jax.jit
    def ttft_staged():       # exact-length oracle: one compile per length
        stage_k = jnp.zeros((1, Gv, T, R), dt).at[:, :, :Lp].set(k_all)
        stage_v = jnp.zeros((1, Gv, T, R), dt).at[:, :, :Lp].set(v_all)
        pk = stage_k[0].reshape(Gv, T // ps, ps, R).transpose(1, 0, 2, 3)
        pv = stage_v[0].reshape(Gv, T // ps, ps, R).transpose(1, 0, 2, 3)
        kpool = kp0.at[phys1].set(pk[:n_prompt_pages])
        vpool = vp0.at[phys1].set(pv[:n_prompt_pages])
        return kq_prefill_paged_attention_op(
            q_all, kpool, vpool, jnp.asarray([Lp], jnp.int32),
            jnp.asarray([0], jnp.int32), btab1, scale=scale, max_len=Lp)

    def mixed_step():        # overlap iteration: decode batch + 1 chunk
        o1 = kq_decode_paged_attention_op(qc2, kp, vp, lens_full,
                                          btab_full, scale=scale,
                                          max_len=T)
        o2, _, _ = prefill_chunk_call(0, kp0, vp0)
        return o1, o2

    @jax.jit
    def fused_step():        # same work as mixed_step, ONE dispatch:
        # the token-budget scheduler's fused iteration (DESIGN.md
        # §scheduler) traces chunk-append + prefill attention + the
        # decode batch into a single jit, so the host pays one launch
        # where mixed_step pays one per op
        pos0 = jnp.asarray([0], jnp.int32)
        kpool = append_chunk(kp0, btab1, pos0, k_ch[0], valid1)
        vpool = append_chunk(vp0, btab1, pos0, v_ch[0], valid1)
        o2 = kq_prefill_paged_attention_op(
            q_ch[0], kpool, vpool, jnp.asarray([C], jnp.int32),
            pos0, btab1, scale=scale, max_len=Lp)
        o1 = kq_decode_paged_attention_op(qc2, kp, vp, lens_full,
                                          btab_full, scale=scale,
                                          max_len=T)
        return o1, o2

    _, us_ttft_c = timed(ttft_chunked)
    _, us_ttft_s = timed(ttft_staged)
    _, us_mixed = timed(mixed_step, reps=5)
    _, us_fused = timed(fused_step, reps=5)
    chunk_buf = 2 * Gv * C * R * kp.dtype.itemsize
    stage_buf = 2 * Gv * T * R * kp.dtype.itemsize
    rows.append(("decode_ttft_chunked", us_ttft_c,
                 f"prompt={Lp};chunk={C};n_chunks={n_chunks};"
                 f"chunk_buf_bytes={chunk_buf};page_writes=direct"))
    rows.append(("decode_ttft_staged", us_ttft_s,
                 f"prompt={Lp};staging_buf_bytes={stage_buf};"
                 f"compiles=per-length"))
    rows.append(("decode_mixed_step", us_mixed,
                 f"decode_B={Bv};chunk={C};overlap=step-level"))
    rows.append(("decode_fused_step", us_fused,
                 f"decode_B={Bv};chunk={C};overlap=one-dispatch"))
    print(f"prefill ttft: chunked {us_ttft_c:.0f}us "
          f"(buf {chunk_buf}B) vs staged {us_ttft_s:.0f}us "
          f"(buf {stage_buf}B); mixed step {us_mixed:.0f}us, "
          f"fused {us_fused:.0f}us ({us_mixed/us_fused:.2f}x)")

    rows.extend(_preemption_rows())
    rows.extend(_shared_prefix_rows())
    rows.extend(_sharded_rows())
    return rows


def _preemption_rows() -> List[Row]:
    """Oversubscribed-pool engine scenario (DESIGN.md §preemption).

    One fixed request batch whose worst cases sum past the small pool,
    served end-to-end three ways on a reduced model: reserve admission
    with an ample pool (the oracle), and optimistic admission over the
    small pool with preempt-and-recompute / preempt-and-swap.  The
    scenario is deliberately tiny and identical in quick and full mode
    — the signal is the *scheduling* overhead quotient, not model
    FLOPs, and each engine is warmed once so jit compiles stay out of
    the timed run (the drain loop is re-enterable: ``generate`` resets
    state via ``start``)."""
    from repro.config import ServeConfig
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    T, ps, n_small = 32, 8, 9
    lens = (14, 13, 14, 13, 14, 13)
    max_new = 6
    # sum of worst cases: 6 requests x ceil(20/8)=3 pages = 18 > 9
    oversub = sum(pages_needed(min(L + max_new, T), ps) for L in lens)

    def mk_reqs():
        rng = np.random.default_rng(0)
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            L).astype(np.int32),
                        max_new_tokens=max_new)
                for i, L in enumerate(lens)]

    base = dict(max_seq_len=T, max_batch=4, temperature=0.0,
                decode_chunk=4, page_size=ps)
    scs = {
        "decode_reserve": ServeConfig(**base),          # ample: full pool
        "decode_preempt_recompute": ServeConfig(
            **base, n_pages=n_small, admission="optimistic"),
        "decode_preempt_swap": ServeConfig(
            **base, n_pages=n_small, admission="optimistic",
            preempt_mode="swap"),
        # sampled invariant auditing (DESIGN.md §robustness): same
        # ample-pool drain as decode_reserve, so the quotient against
        # it prices the audit's host-side cross-checks alone.  The
        # audit walks every page/slot structure, so auditing every
        # step scales with pool size; audit_every=4 bounds that to a
        # quarter of the steps (the n_audits/steps derived fields
        # document the sampling)
        "decode_audit_on": ServeConfig(**base, audit=True,
                                       audit_every=4),
    }
    rows: List[Row] = []
    print("\n== decode_costs: oversubscribed-pool admission scenario ==")
    for name, sc in scs.items():
        eng = ServingEngine(cfg, params, sc)
        eng.generate(mk_reqs())                          # warm compiles
        # engine drains are host-scheduling loops of many small
        # dispatches — noisy on a contended CPU, so give the min
        # estimator a real sample budget
        served, us = timed(lambda e=eng: e.generate(mk_reqs()), reps=3,
                           budget_s=1.5)
        assert all(r.done and not r.failed for r in served)
        extra = ""
        if sc.audit:
            extra = (f";audit_every={sc.audit_every}"
                     f";audits={eng.n_audits}"
                     f";steps={eng._step_count}")
        rows.append((name, us,
                     f"pool_pages={sc.total_pages};"
                     f"worst_case_pages={oversub};"
                     f"preemptions={eng.n_preempted};"
                     f"swaps={eng.n_swapped_out}" + extra))
        print(f"{name}: {us:.0f}us pool={sc.total_pages} "
              f"(worst {oversub}) preemptions={eng.n_preempted} "
              f"swaps={eng.n_swapped_out}")
    return rows


def _shared_prefix_rows() -> List[Row]:
    """Shared-prefix engine scenario (DESIGN.md §prefix-sharing).

    One fixed batch of requests that all carry the same system-prompt
    prefix plus short distinct tails, served end-to-end with
    ``share_prefix=True`` (refcounted pages + prefix index + COW).
    The timed quotient against the ``decode_reserve`` engine drain
    feeds the machine-normalized gate; the derived fields record the
    TTFT work (prefill chunk invocations) and peak pool occupancy of
    the same batch with sharing off, so the row also documents the
    FLOP/HBM saving, not just wall clock."""
    from repro.config import ServeConfig
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    cfg = get_config("tinyllama-1.1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    T, ps, n_prefix, tails = 32, 4, 16, (3, 5, 2, 3, 4, 2)
    max_new = 5

    def mk_reqs():
        rng = np.random.default_rng(1)
        common = rng.integers(0, cfg.vocab_size, n_prefix).astype(np.int32)
        return [Request(rid=i,
                        prompt=np.concatenate(
                            [common,
                             rng.integers(0, cfg.vocab_size,
                                          k).astype(np.int32)]),
                        max_new_tokens=max_new)
                for i, k in enumerate(tails)]

    base = dict(max_seq_len=T, max_batch=4, temperature=0.0,
                decode_chunk=4, page_size=ps, prefill_chunk=ps)
    off = ServingEngine(cfg, params, ServeConfig(**base))
    off.generate(mk_reqs())
    eng = ServingEngine(cfg, params, ServeConfig(**base,
                                                 share_prefix=True))
    eng.generate(mk_reqs())                              # warm compiles
    served, us = timed(lambda: eng.generate(mk_reqs()), reps=3,
                       budget_s=1.5)
    assert all(r.done and not r.failed for r in served)
    print("\n== decode_costs: shared-prefix admission scenario ==")
    print(f"decode_shared_prefix: {us:.0f}us prefill chunks "
          f"{eng.n_prefill_chunks} (unshared {off.n_prefill_chunks}), "
          f"peak pages {eng.peak_used_pages} (unshared "
          f"{off.peak_used_pages}), shared={eng.n_shared_pages} "
          f"forks={eng.n_cow_forks} full_hits={eng.n_full_hits}")
    return [("decode_shared_prefix", us,
             f"prefix={n_prefix};requests={len(tails)};"
             f"prefill_chunks={eng.n_prefill_chunks};"
             f"unshared_prefill_chunks={off.n_prefill_chunks};"
             f"peak_pages={eng.peak_used_pages};"
             f"unshared_peak_pages={off.peak_used_pages};"
             f"shared_pages={eng.n_shared_pages};"
             f"cow_forks={eng.n_cow_forks};"
             f"full_hits={eng.n_full_hits}")]


# the bench process must keep the single real CPU device, so the
# sharded drains fork a subprocess that forces a 4-host-device mesh
# (same idiom as tests/test_multidevice.py) and ships its rows back as
# one JSON line
_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
from benchmarks.common import timed
from repro.config import ServeConfig
from repro.configs import get_config
from repro.models import build_model
from repro.serving import Request, ServingEngine

cfg = get_config("tinyllama-1.1b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
T, ps, B, max_new = 32, 4, 8, 5
lens = (14, 13, 12, 11, 10, 9, 8, 7, 6, 5, 14, 13)


def mk_reqs():
    rng = np.random.default_rng(0)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        L).astype(np.int32),
                    max_new_tokens=max_new)
            for i, L in enumerate(lens)]


base = dict(max_seq_len=T, max_batch=B, temperature=0.0, decode_chunk=4,
            page_size=ps, prefill_chunk=8, n_pages=64)
rows = []
for name, shards in (("decode_sharded_base", 1),
                     ("decode_sharded_pool", 2),
                     ("decode_sharded_step", 4)):
    eng = ServingEngine(cfg, params, ServeConfig(**base, shards=shards))
    eng.generate(mk_reqs())                          # warm compiles
    served, us = timed(lambda e=eng: e.generate(mk_reqs()), reps=3,
                       budget_s=1.5)
    assert all(r.done and not r.failed for r in served)
    steps = eng._step_count
    per_slot = us / (steps * B)
    derived = (f"shards={shards};steps={steps};drain_us={us:.0f};"
               f"slots={B};pooled_pages={eng.pool.n_pages};"
               f"peak_used_pages={eng.peak_used_pages}")
    if shards > 1:
        derived += ";per_shard_peak=" + "/".join(
            str(w.peak_used_pages) for w in eng.workers)
    rows.append((name, per_slot, derived))
print("SHARDED_ROWS " + json.dumps(rows))
"""


def _sharded_rows() -> List[Row]:
    """Data-axis sharded engine drains (DESIGN.md §sharded-engine).

    The same 12-request batch served at shards = 1 / 2 / 4 on a forced
    4-host-device mesh, reported as *per-slot step cost* (drain time /
    steps / slots) so the quotients vs the 1-shard oracle isolate the
    per-step sharding overhead: one sharded dispatch plus host-local
    scheduling, no gathers on the hot path.  Runs in a subprocess on
    forced CPU host devices; a failed child raises."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"       # forced host devices, never a chip
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1200)
    print("\n== decode_costs: data-axis sharded engine drains ==")
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith("SHARDED_ROWS ")), None)
    if r.returncode != 0 or line is None:
        raise RuntimeError(
            f"sharded drains failed (subprocess rc={r.returncode}): "
            f"{r.stderr[-2000:]}")
    rows = [tuple(row) for row in json.loads(line.split(" ", 1)[1])]
    for name, us, derived in rows:
        print(f"{name}: {us:.1f}us/slot-step  {derived}")
    return rows


if __name__ == "__main__":
    run()
