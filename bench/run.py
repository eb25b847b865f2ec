"""Run one cell of the benchmark on the chip(s) this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Prints informational lines, then as the last line of standard output
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
ones), ``device`` (and with ``--trace 1`` a ``breakdown``), and last
``checks``: each number compared for ``correct`` beside its limit.
The checks are also the last lines of standard error.  Exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the
cell asks for.  JAX's compilation cache is kept in ``.jax_cache`` at
the checkout's root unless ``JAX_COMPILATION_CACHE_DIR`` says where.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < cell.chips:
        sys.exit(f"bench: {args.workload} needs {cell.chips} chips, "
                 f"found {len(devs)}")
    log = (lambda s: print(s, flush=True))
    harness.emit(harness.run(cell, args.seed, args.seconds,
                             bool(args.trace), T0, log))


if __name__ == "__main__":
    main()
