"""Serving CLI driver: calibrate, compress with KQ-SVD, serve requests.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --method kqsvd --epsilon 0.1 --requests 4
"""
from __future__ import annotations

import argparse
from typing import List, Optional, Sequence

import jax
import numpy as np

from repro.config import CompressionConfig, ModelConfig, ServeConfig
from repro.configs import get_config
from repro.core.calibration import ModelProjections, calibrate_model
from repro.core.compressed import cache_footprint
from repro.data import calibration_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import Request, ServingEngine


def calibrated_model(cfg: ModelConfig, *, method: str = "kqsvd",
                     epsilon: float = 0.1, calib_seqs: int = 8,
                     calib_len: int = 64, seed: int = 0
                     ) -> tuple[dict, Optional[ModelProjections]]:
    """Weights drawn from ``seed`` plus, for a compressing ``method``,
    projections calibrated on seeded synthetic batches: the
    ``(params, projections)`` pair ``ServingEngine`` serves."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    if method == "none" or cfg.attention_free:
        return params, None
    calib = calibration_batches(cfg.vocab_size, calib_seqs, calib_len,
                                batch=4)
    proj = calibrate_model(model, params,
                           [jax.numpy.asarray(b) for b in calib],
                           CompressionConfig(method=method,
                                             epsilon=epsilon))
    return params, proj


def synthetic_requests(vocab_size: int, lens: Sequence[int],
                       max_new_tokens: int, rng: np.random.Generator, *,
                       shared_frac: float = 0.0,
                       tiers: Sequence[int] = (0,),
                       deadline_steps: Optional[int] = None
                       ) -> List[Request]:
    """One request per prompt length, tokens drawn from ``rng``; the
    first ``shared_frac`` of each prompt comes from one common prefix
    and priorities cycle over ``tiers``."""
    common = rng.integers(0, vocab_size,
                          max(int(max(lens)), 1)).astype(np.int32)
    reqs = []
    for i, n in enumerate(int(x) for x in lens):
        n_common = min(int(round(shared_frac * n)), n - 1)
        tail = rng.integers(0, vocab_size, n - n_common).astype(np.int32)
        reqs.append(Request(rid=i,
                            prompt=np.concatenate([common[:n_common], tail]),
                            max_new_tokens=max_new_tokens,
                            priority=tiers[i % len(tiers)],
                            deadline_steps=deadline_steps))
    return reqs


def latency_line(reqs: Sequence[Request]) -> str:
    """Queue wait (start to admission) and admission to first token,
    p50/p95 in ms over the requests that reached each stamp."""
    def pct(xs):
        if not xs:
            return "n/a"
        p50, p95 = np.percentile(1e3 * np.asarray(xs), [50, 95])
        return f"{p50:.1f}/{p95:.1f} ms"
    waits = [r.t_admitted - r.t_submitted for r in reqs
             if r.t_admitted is not None]
    firsts = [r.t_first_token - r.t_admitted for r in reqs
              if r.t_first_token is not None and r.t_admitted is not None]
    return (f"queue wait p50/p95: {pct(waits)}; admission to first "
            f"token p50/p95: {pct(firsts)}")


def main() -> None:
    """CLI entry: calibrate + compress a (reduced) arch, then drain a
    synthetic request batch through the serving engine, printing the
    per-mode scheduling/pool/sharing/budget reports."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--method", default="kqsvd",
                    choices=["none", "ksvd", "eigen", "kqsvd"])
    ap.add_argument("--epsilon", type=float, default=0.1)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="max prompt length; requests draw mixed lengths "
                         "in [4, prompt-len] (continuous batching)")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="tokens per fused on-device decode scan")
    ap.add_argument("--calib-seqs", type=int, default=8)
    ap.add_argument("--calib-len", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per page of the paged KV cache "
                         "(DESIGN.md §paged-cache)")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="pool size; 0 derives full capacity, smaller "
                         "oversubscribes with admission backpressure")
    ap.add_argument("--shards", type=int, default=1,
                    help="data-axis shards for the serving engine "
                         "(DESIGN.md §sharded-engine): each shard owns "
                         "an equal slice of the slot axis with its own "
                         "page pool and scheduler; one sharded dispatch "
                         "serves the whole batch.  Needs >= shards "
                         "devices (CPU: XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N before launch).  "
                         "1 = unsharded parity oracle.")
    ap.add_argument("--cache-quant", default="none",
                    choices=["none", "int8", "svdq"],
                    help="paged page layout (DESIGN.md §page-layouts): "
                         "int8 = int8 pages + per-page scale pools with "
                         "dequantize-on-the-fly decode; svdq = per-rank "
                         "key bits allocated from the calibrated "
                         "spectrum, packed sub-byte.  Needs a "
                         "compressed --method to take effect.")
    ap.add_argument("--decode-splits", type=int, default=1,
                    help="split-KV flash-decoding fan-out (DESIGN.md "
                         "§split-kv): >1 = fixed, 0 = re-derived per "
                         "step from the live max length (snapped to "
                         "{1,2,4,8}), 1 = unsplit oracle.")
    ap.add_argument("--prefill-chunk", type=int,
                    default=ServeConfig.prefill_chunk,
                    help="tokens per prefill chunk written straight "
                         "into pages (DESIGN.md §prefill)")
    ap.add_argument("--prefill-buckets", default="",
                    help="comma-separated padded chunk lengths (largest "
                         "must equal --prefill-chunk); empty derives by "
                         "doubling")
    ap.add_argument("--max-batched-tokens", type=int, default=0,
                    help="global per-step token budget (DESIGN.md "
                         "§scheduler): each decoding slot charges 1 "
                         "token, prefill chunks fill the remainder "
                         "(the last chunk truncated to it) and one "
                         "chunk fuses into the decode dispatch.  0 = "
                         "legacy per-request scheduling.")
    ap.add_argument("--admission", default="reserve",
                    choices=["reserve", "optimistic"],
                    help="admission policy (DESIGN.md §preemption): "
                         "reserve = worst-case page reservation (the "
                         "parity oracle); optimistic = admit on the "
                         "prompt footprint and preempt-and-requeue LIFO "
                         "victims when the pool runs dry.")
    ap.add_argument("--preempt-mode", default="recompute",
                    choices=["recompute", "swap"],
                    help="victim handling under --admission optimistic: "
                         "recompute the cache from the generated tokens, "
                         "or round-trip the pages through host RAM")
    ap.add_argument("--watermark-high", type=float, default=1.0,
                    help="pool fraction optimistic admission may fill "
                         "(headroom held back for decode growth)")
    ap.add_argument("--watermark-low", type=float, default=0.0,
                    help="extra pool fraction a preemption pass frees "
                         "beyond the strict deficit (thrash guard)")
    ap.add_argument("--admit-window", type=int, default=4,
                    help="pending requests scanned for one that fits "
                         "(avoids head-of-line blocking; 1 = strict FIFO)")
    ap.add_argument("--share-prefix", action="store_true",
                    help="cross-request prefix sharing with copy-on-"
                         "write (DESIGN.md §prefix-sharing): admission "
                         "maps cached prefix pages into the block table "
                         "by reference instead of re-prefilling them.")
    ap.add_argument("--prefix-index-capacity", type=int, default=512,
                    help="max live prefix-index entries (each pins one "
                         "page until reclaimed; LRU beyond this)")
    ap.add_argument("--shared-frac", type=float, default=0.0,
                    help="fraction of each prompt drawn from one common "
                         "prefix (demo workload for --share-prefix)")
    ap.add_argument("--priority", default="",
                    help="comma-separated Request.priority tiers, cycled "
                         "over the requests (empty = all tier 0); under "
                         "--admission optimistic, preemption evicts "
                         "lower tiers first")
    ap.add_argument("--deadline-steps", type=int, default=0,
                    help="per-request total step budget (DESIGN.md "
                         "§robustness); a request not finished within "
                         "this many engine steps fails with "
                         "error.kind=deadline.  0 = unbounded")
    ap.add_argument("--audit", action="store_true",
                    help="cross-check pool refcounts / free list / "
                         "block tables after every engine step "
                         "(invariants.audit; DESIGN.md §robustness)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="arm a seeded chaos FaultInjector: every "
                         "recoverable fault point fires with "
                         "probability --chaos-rate per hit, "
                         "reproducibly (DESIGN.md §robustness)")
    ap.add_argument("--chaos-rate", type=float, default=0.05,
                    help="per-hit fault probability under --chaos-seed")
    args = ap.parse_args()
    enable_compile_cache()
    if args.shards > 1 and args.n_pages % args.shards:
        n = -(-args.n_pages // args.shards) * args.shards
        print(f"--shards needs equal per-shard pools: rounding "
              f"--n-pages {args.n_pages} up to {n}")
        args.n_pages = n

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params, proj = calibrated_model(cfg, method=args.method,
                                    epsilon=args.epsilon,
                                    calib_seqs=args.calib_seqs,
                                    calib_len=args.calib_len)
    if proj is not None:
        fp = cache_footprint(max(cfg.n_kv_heads, 1), cfg.d_head or 1,
                             proj.rank_k, proj.rank_v)
        print(f"calibrated {args.method}: ranks k={proj.ranks_k} "
              f"v={proj.ranks_v}; cache ratio {fp.ratio:.3f}")

    T = args.prompt_len + args.max_new_tokens + 8
    T = -(-T // args.page_size) * args.page_size   # whole pages
    buckets = tuple(int(x) for x in args.prefill_buckets.split(",")
                    if x.strip())
    sc = ServeConfig(max_seq_len=T, max_batch=8,
                     decode_chunk=args.decode_chunk,
                     page_size=args.page_size, n_pages=args.n_pages,
                     prefill_chunk=args.prefill_chunk,
                     prefill_buckets=buckets,
                     admission=args.admission,
                     preempt_mode=args.preempt_mode,
                     watermark_high=args.watermark_high,
                     watermark_low=args.watermark_low,
                     admit_window=args.admit_window,
                     share_prefix=args.share_prefix,
                     prefix_index_capacity=args.prefix_index_capacity,
                     audit=args.audit,
                     chaos_seed=args.chaos_seed,
                     chaos_rate=args.chaos_rate,
                     max_num_batched_tokens=args.max_batched_tokens,
                     cache_quant=args.cache_quant,
                     decode_splits=args.decode_splits,
                     shards=args.shards)
    eng = ServingEngine(cfg, params, sc, projections=proj)
    rng = np.random.default_rng(0)
    lens = rng.integers(min(4, args.prompt_len), args.prompt_len + 1,
                        args.requests)
    tiers = [int(x) for x in args.priority.split(",") if x.strip()] or [0]
    reqs = synthetic_requests(cfg.vocab_size, lens, args.max_new_tokens,
                              rng, shared_frac=args.shared_frac,
                              tiers=tiers,
                              deadline_steps=args.deadline_steps or None)
    eng.generate(reqs)
    for r in reqs:
        note = "  [truncated]" if r.truncated else ""
        if r.failed:
            # structured failure taxonomy (DESIGN.md §robustness):
            # kind + cause + the engine step it happened on
            note = (f"  [failed: {r.error.kind} @ step {r.error.step}"
                    + (f" — {r.error.detail}" if r.error.detail else "")
                    + "]")
        print(f"req {r.rid} (prompt {len(r.prompt):3d}): "
              f"{r.out_tokens}{note}")
    print(f"capacity gain vs full cache: {eng.capacity_gain():.2f}x")
    print(latency_line(reqs))
    if eng.n_failed:
        kinds = ", ".join(f"{k}={n}" for k, n in
                          eng.error_counts.items() if n)
        print(f"failures: {eng.n_failed} ({kinds})")
    if args.chaos_seed is not None and eng.faults is not None:
        fired = eng.faults.points_fired()
        print(f"chaos(seed={args.chaos_seed}, rate={args.chaos_rate}): "
              f"{len(eng.faults.fired_log)} fault(s) fired at "
              f"{list(fired) or 'no points'}; "
              f"retries={eng.n_retried}, "
              f"swap fallbacks={eng.n_swap_fallbacks}")
    pool = eng.pool
    print(f"page pool: {pool.n_pages} x {args.page_size}-token "
          f"pages, {pool.free_count} free after drain")
    if args.shards > 1:
        # pooled capacity across the data mesh: shards x the
        # per-shard physical pool (already scaled by the layout's
        # resident-capacity multiplier, DESIGN.md §sharded-engine)
        print(f"sharded: {args.shards} shard(s) x "
              f"{eng._local_phys} physical page(s) = "
              f"{pool.n_pages} pooled "
              f"(x{eng.workers[0].capacity_x:.2f} resident "
              f"capacity multiplier); per-shard occupancy: "
              + ", ".join(
                  f"s{w._shard}={w.pool.used_count}"
                  f"/{w.pool.n_pages}" for w in eng.workers))
    print(f"admission={args.admission}: preemptions="
          f"{eng.n_preempted} (swap out/in {eng.n_swapped_out}/"
          f"{eng.n_swapped_in}), failed={eng.n_failed}")
    if args.cache_quant != "none":
        # page-layout capacity story (DESIGN.md §page-layouts):
        # packed vs fp bytes per cached token at the served ranks
        from repro.serving.page_layouts import FpLayout, get_layout
        lay = get_layout(eng.cfg)
        rk, rv = eng.ranks
        if eng.cfg.cache_quant == "none":
            print(f"cache quant {args.cache_quant}: inert "
                  f"(no compression projections; fp pages served)")
        else:
            fp = FpLayout()
            packed = (lay.token_bytes("k", rk)
                      + lay.token_bytes("v", rv))
            full = (fp.token_bytes("k", rk)
                    + fp.token_bytes("v", rv))
            print(f"cache quant {args.cache_quant}: "
                  f"{packed} packed vs {full} fp byte(s)/token "
                  f"-> {full / packed:.2f}x resident capacity")
    if args.share_prefix:
        print(f"prefix sharing: {eng.n_shared_pages} page(s) / "
              f"{eng.n_shared_tokens} token(s) shared, "
              f"{eng.n_full_hits} whole-prompt hit(s), "
              f"{eng.n_cow_forks} COW fork(s), "
              f"{eng.n_reclaimed} index entr(ies) reclaimed; "
              f"peak pool occupancy {eng.peak_used_pages} page(s)")
    print(f"prefill compiles: {len(eng.prefill_chunk_shapes)} chunk "
          f"shape(s) {sorted(eng.prefill_chunk_shapes)} of "
          f"{len(sc.buckets)} bucket(s) {list(sc.buckets)}")
    if args.max_batched_tokens:
        # per-step budget accounting (DESIGN.md §scheduler): how the
        # global token budget split between decode charges and prefill
        # fill, and how often a chunk fused into the decode dispatch
        log = eng.budget_log
        dec = sum(e["n_decode"] for e in log)
        pf = sum(e["prefill_tokens"] for e in log)
        print(f"token budget {args.max_batched_tokens}/step over "
              f"{len(log)} step(s): {dec} decode + {pf} prefill "
              f"token(s) scheduled, {eng.n_fused_steps} fused "
              f"iteration(s), {eng.n_truncated_chunks} chunk(s) "
              f"truncated at the residual budget")
        for e in log[:12]:
            print(f"  step {e['step']:3d}: budget={e['budget']:3d} "
                  f"decode={e['n_decode']:2d} "
                  f"prefill={e['prefill_tokens']:3d} "
                  f"admitted={e['admitted']}"
                  + ("  [fused]" if e["fused"] else ""))
        if len(log) > 12:
            print(f"  ... {len(log) - 12} more step(s)")


if __name__ == "__main__":
    main()
