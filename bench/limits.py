"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/limits.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --seconds <run_seconds> [--out FILE]

In one process (set-up is long), for each seed: weights and projections
from the seed, the cell's backlog served through the timed path for the
window that a run measures, and ``harness.check`` on what it served (the
program's reading: the widest gap by which a served token's logit lies
below the reference's best).  For the control seeds, also two controls,
each through ``harness.check`` as well, so that each shows its
``correct``: the program with its own lower-precision path switched on
(``cache_quant="int8"`` pages), served and checked like the program; and
the reference computed with float8 e4m3 matmul operands in the
program's place, read at the token it puts first at each position of
the same prompts and served tokens.  One JSON line per seed on stdout
(and in ``--out``).

``--dump-trace FILE`` instead runs one traced window and writes the
reduced trace, trimmed, with the most time-consuming operation names:
the way the test data under ``bench/tests/data`` was recorded.
"""
import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--dump-trace", default="")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        sys.exit("limits: needs a TPU")
    from bench import harness, tracing
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def served(c, built, seed, trace=False):
        t0 = time.perf_counter()
        eng = harness.make_engine(c, built)
        reqs = harness.backlog(c, seed, built.dims.vocab)
        harness.warm_up(eng, reqs, built.dims.vocab)
        win = harness.serve(eng, reqs, args.seconds, trace)
        del eng
        gc.collect()
        jax.clear_caches()
        return reqs, win, win.t_open - t0

    def reading(checks):
        return {"gap": checks["logit_gap"]["value"],
                "tokens": checks["tokens_checked"]["value"],
                "correct": harness.checks_pass(checks)}

    if args.dump_trace:
        seeds = seeds[:1]
    for seed in seeds:
        built = harness.build(cell, seed)
        jax.clear_caches()
        if args.dump_trace:
            served(cell, built, seed, trace=True)
            tr = tracing.load(harness.TRACE_DIR)
            rec = {"top_ops": tracing.top_ops(tr, 40),
                   "modules": sorted({m[0] for d in tr["devices"]
                                      for m in d["modules"]}),
                   "trimmed": tracing.trimmed(tr)}
            Path(args.dump_trace).write_text(json.dumps(rec))
            print(json.dumps({k: rec[k] for k in rec if k != "trimmed"}),
                  flush=True)
            return
        reqs, win, engine_s = served(cell, built, seed)
        t1 = time.perf_counter()
        checks = harness.check(built, cell, reqs, win, seed)
        lim = cell.workload["check"]
        rec = {"workload": args.workload, "seed": seed,
               "program": reading(checks),
               "requests": [[len(r.prompt), len(r.out_tokens)]
                            for r in harness.sample(
                                reqs, win, seed, lim["sample_tokens"],
                                lim["max_requests"])],
               "failed": checks["failed_requests"]["value"],
               "reference_s": time.perf_counter() - t1,
               "ref_calib_s": built.ref_s, "engine_s": engine_s,
               "window_s": win.seconds,
               "tok_per_s": sum(d.k for d in win.deliveries) / win.seconds,
               "compiles_in_window": win.compiles}
        if seed in control:
            rec["control_fp8"] = reading(
                harness.check(built, cell, reqs, win, seed, control="fp8"))
            c8 = cell.serving(cache_quant="int8")
            reqs8, win8, _ = served(c8, built, seed)
            rec["control_int8"] = reading(
                harness.check(built, c8, reqs8, win8, seed))
        emit(rec)
        del built, reqs
        gc.collect()


if __name__ == "__main__":
    main()
