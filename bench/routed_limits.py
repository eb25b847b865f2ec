"""Readings that the limits of ``correct`` are set from, on the chip, for
a cell whose reference leaves router near-ties unscored
(``mimo_reference``: ``ROUTE_MARGIN``, ``hidden`` returning margins).

    python3 bench/routed_limits.py --workload mimov2f-mooncake \
        --seeds 1,2,3 --seconds <run_seconds> [--trace 0|1] \
        [--out FILE] [--arrays DIR]

``bench/limits.py`` also serves its int8-page control, which a program
serving window rings refuses; this reads the program and the float8
control only.  In one process (set-up is long), for each seed: one run
of the cell as ``bench/run.py`` makes it (``harness.run``, traced with
``--trace 1``), and on the requests its check sampled, at every checked
position, the program's gap and the float8 control's (as
``harness.check`` computes them, scored and unscored) and the
reference's router margin.  One JSON
line per seed on stdout (and in ``--out``): the run's metrics and
``correct``, the largest gaps with and without the near-ties, the
positions left unscored, and how many of those the program read a gap
at; ``--arrays`` also keeps each seed's per-position gaps and margins
(``<seed>.npz``), from which another ``ROUTE_MARGIN`` can be read
without the chip, and its whole result line (``<seed>.json``).
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]


def readings(built, cell, reqs, win, seed) -> dict:
    """Per checked position (the requests ``harness.check`` samples): the
    program's gap and the float8 control's, both unscored, and the
    reference's router margin."""
    import jax.numpy as jnp
    import numpy as np
    from bench import harness
    ref, d, w = built.ref, built.dims, built.weights
    lim = cell.workload["check"]
    picked = harness.sample(reqs, win, seed, lim["sample_tokens"],
                            lim["max_requests"])
    proj = {k: jnp.asarray(v) for k, v in built.proj.items()}
    out = {"gap": [], "control": [], "margin": []}
    for r in picked:
        full = np.concatenate([r.prompt, np.asarray(r.out_tokens,
                                                    np.int32)])
        P, n = len(r.prompt), len(r.out_tokens)
        toks = jnp.asarray(harness._padded(full[:-1]))
        nxt = jnp.asarray(harness._padded(full[1:]))
        hid = ref.hidden(d, w, proj, toks, "f32")
        alt = ref.hidden(d, w, proj, toks, "fp8")
        rows = slice(P - 1, P - 1 + n)
        out["gap"].append(np.asarray(
            ref.gaps_to_tokens(w, hid, nxt, route_margin=0.0))[rows])
        out["control"].append(np.asarray(
            ref.gaps_to_alt(w, hid, alt, "fp8", route_margin=0.0))[rows])
        out["margin"].append(np.asarray(hid[1])[rows])
    return {k: (np.concatenate(v) if v else np.zeros(0))
            for k, v in out.items()}


def summary(arr: dict, route_margin: float) -> dict:
    """The largest gaps, scored at ``route_margin`` and unscored, and
    the positions left unscored."""
    import numpy as np
    scored = arr["margin"] >= route_margin

    def top(x):
        return float(x.max()) if x.size else None

    return {"route_margin": route_margin, "tokens": int(scored.size),
            "gap": top(np.where(scored, arr["gap"], 0.0)),
            "gap_unscored_too": top(arr["gap"]),
            "control": top(np.where(scored, arr["control"], 0.0)),
            "control_unscored_too": top(arr["control"]),
            "unscored": int((~scored).sum()),
            "unscored_with_gap": int(((~scored) & (arr["gap"] > 0)).sum())}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--arrays", default="")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        sys.exit("routed_limits: needs a TPU")
    from bench import harness
    cell = harness.load_cell(args.workload)
    conf = cell.conf
    import importlib
    ref = importlib.import_module(f"bench.configs.{conf['reference']}")
    out = open(args.out, "a") if args.out else None
    if args.arrays:
        Path(args.arrays).mkdir(parents=True, exist_ok=True)
    check, seen = harness.check, {}

    def check_and_read(built, c, reqs, win, seed, control=None):
        # harness.run's own check, then the readings on what it sampled
        seen["arrays"] = readings(built, c, reqs, win, seed)
        return check(built, c, reqs, win, seed, control)

    harness.check = check_and_read
    t0 = T0
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        res = harness.run(cell, seed, args.seconds, bool(args.trace), t0,
                          log=lambda s: print(s, flush=True))
        arr = seen.pop("arrays")
        if args.arrays:
            np.savez(Path(args.arrays) / f"{seed}.npz", **arr)
            (Path(args.arrays) / f"{seed}.json").write_text(json.dumps(res))
        rec = {"workload": args.workload, "seed": seed,
               "correct": res["correct"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "device": res["device"],
               "logit_gap": res["checks"]["logit_gap"],
               **summary(arr, ref.ROUTE_MARGIN)}
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        t0 = time.perf_counter()


if __name__ == "__main__":
    main()
