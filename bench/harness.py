"""The benchmark harness: one cell, one seed, one run.

A cell (``bench/workloads/<cell>.json``) names a configuration
(``bench/configs/<config>.json``), a traffic mix
(``bench/traffic/<mix>.json``) and the serving settings of its
deployment.  A run:

1. makes the weights on the device from the seed and solves fixed-rank
   KQ-SVD projections on seeded calibration sequences, both with the
   configuration's plain reference (the program makes neither);
2. builds the program's ``ServingEngine`` and warms every shape the
   cell's backlog will use (prefill buckets, and the decode dispatch at
   every context length the backlog reaches);
3. hands the whole backlog to ``start`` and steps until the first wave
   of ``max_batch`` requests has its first tokens (the lead-in);
4. measures for ``seconds``: the window closes at the first step
   boundary after that, and every delivery of tokens is logged with
   the host clock at the step boundary that brought it;
5. reads the device's peak memory, frees the engine, and compares a
   seeded sample of the requests finished in the window with the
   reference (``check``);
6. hands the run to each metric's reader (``bench/metrics/<name>.py``).

Everything up to the window's opening is set-up (``setup_s``), except
the reference's own work: the calibration passes and the KQ-SVD solve,
which stand in for a deployment's stored projections.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from bench import cost, tracing, traffic

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
GIB = 2 ** 30
#: SeedSequence streams drawn from the run's seed
STREAM_WEIGHTS, STREAM_CALIB, STREAM_SAMPLE = 0, 1, 3
#: traced span inside the window: starts this far in, lasts this long
TRACE_DELAY_S, TRACE_S = 2.0, 12.0
TRACE_DIR = ROOT / ".bench_trace"

#: programs compiled or loaded from the persistent cache so far in this
#: process, counted by a JAX monitoring listener added once
_COMPILES = {"n": 0, "listening": False}


def _count_compiles(event: str, duration: float, **_kw) -> None:
    if event.endswith(("backend_compile_duration",
                       "cache_retrieval_time_sec")):
        _COMPILES["n"] += 1


def _listen_for_compiles() -> None:
    import jax
    if not _COMPILES["listening"]:
        jax.monitoring.register_event_duration_secs_listener(
            _count_compiles)
        _COMPILES["listening"] = True


@dataclasses.dataclass
class Cell:
    """A workload with its configuration and traffic mix, as loaded."""
    name: str
    workload: dict
    conf: dict
    mix: dict

    @property
    def chips(self) -> int:
        return self.workload["chips"]

    def serving(self, **settings) -> "Cell":
        """The same cell with some serving settings changed (the
        program's own lower-precision path, for the control)."""
        wl = json.loads(json.dumps(self.workload))
        wl["serve"].update(settings)
        return Cell(self.name, wl, self.conf, self.mix)


def load_cell(name: str) -> Cell:
    wl = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    conf = json.loads((BENCH / "configs" / f"{wl['config']}.json")
                      .read_text())
    return Cell(name, wl, conf, traffic.load(wl["traffic"]))


def metric_specs(cell: str, per_layer: bool) -> list:
    """The metrics of ``BENCHMARK.json`` that this cell reports: its
    end-to-end metrics, or with ``per_layer`` its per-layer ones."""
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    group = bm["per_layer" if per_layer else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(metric: str) -> Callable:
    """``read`` of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def in_use_gib() -> float:
    """Device memory in use now (first device), GiB; 0 where the
    backend does not say."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_in_use", 0) / GIB


def seed_words(seed: int, stream: int) -> np.ndarray:
    """Two 32-bit words from (seed, stream): a raw PRNG key."""
    return np.random.SeedSequence([seed, stream]).generate_state(2)


def serve_config(settings: dict):
    """The program's ``ServeConfig`` from a cell's settings, passing only
    the fields it still has; returns it and the settings it dropped."""
    from repro.config import ServeConfig
    fields = {f.name for f in dataclasses.fields(ServeConfig)}
    kept = {k: v for k, v in settings.items() if k in fields}
    return ServeConfig(**kept), sorted(set(settings) - fields)


# ---------------------------------------------------------------------------
# building
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Built:
    dims: object
    ref: object              # the configuration's reference module
    weights: dict            # reference layout, on the device
    proj: dict               # KQ-SVD factors, bfloat16 values in float32
    ranks: tuple
    ref_s: float = 0.0       # seconds of calibration and KQ-SVD solve


def build(cell: Cell, seed: int) -> Built:
    """Weights from the seed and fixed-rank KQ-SVD projections, both
    made by the configuration's reference."""
    import jax
    import jax.numpy as jnp
    conf = cell.conf
    ref = importlib.import_module(f"bench.configs.{conf['reference']}")
    d = ref.Dims.of(conf)
    init = jax.jit(lambda k: ref.init_weights(d, k))
    w = init(jnp.asarray(seed_words(seed, STREAM_WEIGHTS), jnp.uint32))
    jax.block_until_ready(w)
    t0 = time.perf_counter()
    kq = conf["kqsvd"]
    rng = np.random.default_rng([seed, STREAM_CALIB])
    grams = None
    for _ in range(kq["calib_seqs"]):
        toks = rng.integers(0, d.vocab, kq["calib_len"]).astype(np.int32)
        g = ref.calibration_grams(d, w, jnp.asarray(toks))
        grams = g if grams is None else tuple(a + b
                                              for a, b in zip(grams, g))
    grams = tuple(np.asarray(g, np.float64) for g in grams)
    proj = ref.solve_kqsvd(d, grams, np.asarray(w["wo"]), kq["rank_k"],
                           kq["rank_v"])
    # served in bfloat16: the engine and the reference see these values
    proj = {k: v.astype(jnp.bfloat16).astype(np.float32)
            for k, v in proj.items()}
    return Built(d, ref, w, proj, (kq["rank_k"], kq["rank_v"]),
                 time.perf_counter() - t0)


def make_engine(cell: Cell, built: Built, log=print):
    """The program's engine over the benchmark's weights and factors."""
    from repro.serving import ServingEngine
    prog = importlib.import_module(f"bench.configs.{cell.conf['program']}")
    sc, dropped = serve_config(cell.workload["serve"])
    if dropped:
        log(f"[bench] ServeConfig has no field for {dropped}: dropped")
    return ServingEngine(prog.model_config(cell.conf),
                         prog.params(built.weights), sc,
                         projections=prog.projections(built.proj))


def backlog(cell: Cell, seed: int, vocab: int) -> list:
    from repro.serving import Request
    return [Request(rid=i, prompt=p, max_new_tokens=o)
            for i, (p, o) in enumerate(traffic.generate(cell.mix, seed,
                                                        vocab))]


def warm_lengths(longest: int, chunk: int) -> list:
    """Prompt lengths that take the decode dispatch through the contexts
    the backlog reaches from one prefill chunk up: ``chunk`` times each
    power of two below ``longest``, then ``longest`` less 16 tokens of
    room.  (The prefill buckets' warm-up reaches those under a chunk.)"""
    out, n = [], chunk
    while n < longest - 16:
        out.append(n)
        n *= 2
    return out + [max(1, longest - 16)]


def warm_up(eng, reqs: list, vocab: int) -> None:
    """On throwaway requests: every prefill bucket the backlog's chunks
    fall in; then, one request at a time, a decode at each context
    length of ``warm_lengths``, so that a program the engine picks by
    the live context (such as its split count) is ready before the
    window."""
    from repro.serving import Request
    sc = eng.sc
    lens = sorted({sc.bucket_for(n) for r in reqs
                   for _, n in cost.chunks(len(r.prompt), sc.prefill_chunk)})
    rng = np.random.default_rng(0)

    def req(i, n):
        return Request(rid=-1 - i, max_new_tokens=2,
                       prompt=rng.integers(0, vocab, n).astype(np.int32))
    eng.generate([req(i, n) for i, n in enumerate(lens)])
    longest = min(sc.max_seq_len,
                  max(len(r.prompt) + r.max_new_tokens for r in reqs))
    for n in warm_lengths(longest, sc.prefill_chunk):
        eng.generate([req(len(lens) + n, n)])


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Delivery:
    """Tokens that reached the host for one request at one step end."""
    step: int
    t: float                 # host clock at the step boundary
    rid: int
    k: int                   # tokens it brought
    before: int              # tokens the request had before it
    prompt_len: int
    finished: bool
    gap: Optional[float]     # seconds per token since the last delivery


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float = 0.0
    deliveries: list = dataclasses.field(default_factory=list)
    steps: list = dataclasses.field(default_factory=list)   # step ends
    trace_span: Optional[tuple] = None      # (first, last) traced step
    compiles: int = 0
    gc_pauses: list = dataclasses.field(default_factory=list)  # seconds

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


class Tracker:
    """Turns request state after each step into deliveries."""

    def __init__(self, reqs: list, width: int):
        self.reqs = reqs
        self.width = width
        self.lo = 0
        self.seen = {}
        self.last = {}

    def collect(self, step: int, now: float) -> list:
        out = []
        reqs = self.reqs
        while self.lo < len(reqs):          # skip requests fully seen
            r = reqs[self.lo]
            if not ((r.done or r.failed)
                    and self.seen.get(r.rid, 0) == len(r.out_tokens)):
                break
            self.lo += 1
        for r in reqs[self.lo: self.lo + self.width]:
            n, before = len(r.out_tokens), self.seen.get(r.rid, 0)
            if n > before:
                k = n - before
                gap = ((now - self.last[r.rid]) / k
                       if r.rid in self.last else None)
                out.append(Delivery(step, now, r.rid, k, before,
                                    len(r.prompt), bool(r.done), gap))
                self.seen[r.rid] = n
                self.last[r.rid] = now
        return out


class _GcTimer:
    """A ``gc.callbacks`` entry that keeps each collection's seconds."""

    def __init__(self):
        self.pauses, self._t = [], 0.0

    def __call__(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)


def serve(eng, reqs: list, seconds: float, trace: bool,
          log=print) -> Window:
    """Lead-in, then the measured window (see the module docstring).
    Returns the window's log; with ``trace`` a few seconds inside it are
    profiled into ``TRACE_DIR``, and the window is lengthened by the time
    the profiler takes to start and stop, so that a traced run serves as
    much as an untraced one."""
    import jax
    sc = eng.sc
    tracker = Tracker(reqs, 2 * sc.max_batch + 16)
    eng.start(reqs)
    step = 0
    while not all(r.out_tokens or r.done or r.failed
                  for r in reqs[: sc.max_batch]):
        if not eng.step():
            break
        step += 1
        tracker.collect(step, time.perf_counter())
    # what set-up left alive (the modules, the backlog, the engine) is
    # moved out of the collector's sight, so that a full collection in
    # the window scans only what the window allocates
    gc.collect()
    gc.freeze()
    gc_timer = _GcTimer()
    gc.callbacks.append(gc_timer)
    win = Window(t_open=time.perf_counter(), gc_pauses=gc_timer.pauses)
    compiles0 = _COMPILES["n"]
    delay = min(TRACE_DELAY_S, seconds / 4)
    span = min(TRACE_S, seconds / 2)
    tracer = contextlib.ExitStack()
    traced_from = None
    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda _name: contextlib.nullcontext()))
    busy = True
    paused = 0.0          # starting and stopping the profiler: not served
    try:
        while busy:
            now = time.perf_counter()
            if trace and traced_from is None and now - win.t_open >= delay:
                tracer.enter_context(tracing.capture(TRACE_DIR))
                traced_from = (step + 1, time.perf_counter())
                paused += traced_from[1] - now
            if (traced_from is not None and win.trace_span is None
                    and now - traced_from[1] >= span):
                tracer.close()
                win.trace_span = (traced_from[0], step)
                paused += time.perf_counter() - now
            with annotate("bench.step"):
                busy = eng.step()
            step += 1
            now = time.perf_counter()
            with annotate("bench.collect"):
                got = tracker.collect(step, now)
            win.deliveries.extend(got)
            win.steps.append((step, now, len(got)))
            if now - win.t_open - paused >= seconds:
                break
    finally:
        if traced_from is not None and win.trace_span is None:
            tracer.close()
            win.trace_span = (traced_from[0], step)
    win.t_close = time.perf_counter()
    win.compiles = _COMPILES["n"] - compiles0
    gc.callbacks.remove(gc_timer)
    gc.unfreeze()
    if not busy:
        log("[bench] the backlog ran dry inside the window")
    return win


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def sample(reqs: list, win: Window, seed: int, target_tokens: int,
           max_requests: int) -> list:
    """Requests finished in the window, drawn from the seed: the longest
    (prompt plus answer) first, then others until ``target_tokens``
    served tokens or ``max_requests``."""
    done_ids = {d.rid for d in win.deliveries if d.finished}
    done = [r for r in reqs if r.rid in done_ids and r.done
            and not r.failed]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.prompt) + len(r.out_tokens))
    rng = np.random.default_rng([seed, STREAM_SAMPLE])
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    out, n = [longest], len(longest.out_tokens)
    for r in rest:
        if n >= target_tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += len(r.out_tokens)
    return out


#: the reference pads each sequence to a multiple of this, so a cell's
#: checks compile a handful of shapes
REF_PAD = 1024


def _padded(seq: np.ndarray, block: int = REF_PAD) -> np.ndarray:
    n = -(-len(seq) // block) * block
    return np.pad(seq, (0, n - len(seq)))


def gaps(built: Built, reqs: list, control: Optional[str] = None
         ) -> np.ndarray:
    """For each served token of ``reqs``: how far its logit lies below
    the reference's best at that position.  With ``control`` (a lower
    precision of the reference), the gap of the token that precision
    puts first instead, at the same positions."""
    import jax.numpy as jnp
    ref, d = built.ref, built.dims
    proj = {k: jnp.asarray(v) for k, v in built.proj.items()}
    out = []
    for r in reqs:
        full = np.concatenate([r.prompt, np.asarray(r.out_tokens,
                                                    np.int32)])
        P, n = len(r.prompt), len(r.out_tokens)
        toks = jnp.asarray(_padded(full[:-1]))
        nxt = jnp.asarray(_padded(full[1:]))
        hid = ref.hidden(d, built.weights, proj, toks, "f32")
        if control is None:
            g = ref.gaps_to_tokens(built.weights, hid, nxt)
        else:
            alt = ref.hidden(d, built.weights, proj, toks, control)
            g = ref.gaps_to_alt(built.weights, hid, alt, control)
        out.append(np.asarray(g)[P - 1: P - 1 + n])
    return np.concatenate(out) if out else np.zeros(0)


def check(built: Built, cell: Cell, reqs: list, win: Window,
          seed: int, control: Optional[str] = None) -> dict:
    """The numbers compared, each beside its limit.  With ``control``
    the gaps are the control's (see ``gaps``): it has to fail them."""
    lim = cell.workload["check"]
    picked = sample(reqs, win, seed, lim["sample_tokens"],
                    lim["max_requests"])
    g = gaps(built, picked, control)
    short = sum(1 for r in picked
                if len(r.out_tokens) != r.max_new_tokens)
    failed = sum(1 for r in reqs if r.failed)
    return {
        "logit_gap": {"value": float(g.max()) if g.size else None,
                      "limit": lim["logit_gap"]},
        "tokens_checked": {"value": int(g.size),
                           "limit": lim["min_tokens_checked"]},
        "short_answers": {"value": short, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
    }


def checks_pass(checks: dict) -> bool:
    c = checks
    if any(v["value"] is None or v["limit"] is None for v in c.values()):
        return False
    return (c["logit_gap"]["value"] <= c["logit_gap"]["limit"]
            and c["tokens_checked"]["value"]
            >= c["tokens_checked"]["limit"]
            and c["short_answers"]["value"] <= 0
            and c["failed_requests"]["value"] <= 0)


# ---------------------------------------------------------------------------
# what the metric readers see
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """One run's record, as the readers in ``bench/metrics`` take it."""
    window: Window
    setup_s: float
    memory_peak_bytes: int
    peak_used_pages: int
    n_pages: int
    dims: object
    ranks: tuple
    prefill_chunk: int
    peaks: dict
    trace: Optional[dict] = None

    def in_trace(self) -> list:
        """Deliveries of the traced steps."""
        if self.window.trace_span is None:
            return []
        a, b = self.window.trace_span
        return [d for d in self.window.deliveries if a <= d.step <= b]

    def trace_seconds(self) -> float:
        lo, hi = tracing.window(self.trace)
        return (hi - lo) / 1e9


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
        log=print, peaks_of: Optional[str] = None) -> dict:
    """One run of a cell; returns the result line as a dict.
    ``peaks_of`` names the device kind whose peaks the readers use
    (default: the device's own; rehearsals off the chip pass one)."""
    import jax
    _listen_for_compiles()
    devs = jax.devices()
    kind = devs[0].device_kind
    pk = peaks(peaks_of or kind)
    t1 = time.perf_counter()
    built = build(cell, seed)
    # the weights' and the calibration's programs hold device memory
    # while loaded; the engine's programs need it
    jax.clear_caches()
    t2 = time.perf_counter()
    eng = make_engine(cell, built, log)
    reqs = backlog(cell, seed, built.dims.vocab)
    warm_up(eng, reqs, built.dims.vocab)
    t3 = time.perf_counter()
    win = serve(eng, reqs, seconds, trace, log)
    log(f"[bench] set-up: start {t1 - t0:.3f}s, weights "
        f"{t2 - t1 - built.ref_s:.3f}s, engine and warm-up {t3 - t2:.3f}s, "
        f"lead-in {win.t_open - t3:.3f}s; not counted: reference "
        f"calibration and KQ-SVD solve {built.ref_s:.3f}s; "
        f"{in_use_gib():.3f} GiB in use")
    setup_s = win.t_open - t0 - built.ref_s
    mem = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for dv in devs)
    rec = Run(window=win, setup_s=setup_s, memory_peak_bytes=int(mem),
              peak_used_pages=int(eng.peak_used_pages),
              n_pages=int(eng.pool.n_pages), dims=built.dims,
              ranks=built.ranks, prefill_chunk=eng.sc.prefill_chunk,
              peaks=pk)
    ends = [win.t_open] + [t for _, t, _ in win.steps]
    dur = np.diff(ends) if len(ends) > 1 else np.zeros(1)
    log(f"[bench] setup {setup_s:.3f}s, window {win.seconds:.3f}s, "
        f"{sum(d.k for d in win.deliveries)} tokens, {len(win.steps)} "
        f"steps, {win.compiles} compiles in the window; step median "
        f"{np.median(dur):.4f}s, slowest {dur.max():.4f}s (step "
        f"{int(dur.argmax()) + 1}); {len(win.gc_pauses)} collections, "
        f"longest {max(win.gc_pauses, default=0.0):.4f}s")
    del eng
    gc.collect()
    jax.clear_caches()
    checks = check(built, cell, reqs, win, seed)
    result = {"correct": checks_pass(checks),
              "attempted": len({d.rid for d in win.deliveries}),
              "failed": checks["failed_requests"]["value"]}
    if trace:
        rec.trace = tracing.load(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    specs = metric_specs(cell.name, per_layer=trace)
    metrics = {}
    for m in specs:
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    result.update(metrics=metrics, device=device)
    if trace:
        tr = rec.trace
        device["busy_s"] = (sum(tracing.busy_ns(tr, dv)
                                for dv in tr["devices"])
                            / max(1, len(tr["devices"])) / 1e9)
        device["window_s"] = rec.trace_seconds()
        result["breakdown"] = {"device_ops": tracing.top_ops(tr),
                               "idle_gaps": tracing.idle_gaps(tr)}
    result["compiles_in_window"] = win.compiles
    result["checks"] = checks
    return result


def emit(result: dict) -> None:
    """Checks as the last lines of stderr, the result as the last line
    of stdout."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
