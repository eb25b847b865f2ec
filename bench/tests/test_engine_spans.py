"""The engine's host spans in a live profiler trace of the tiny cell
served through the harness on the CPU: every span appears, nests inside
``engine.step`` (itself inside the harness's ``bench.step``), and a plain
decoding step makes a fixed sequence of device-to-host reads."""
import time
from pathlib import Path

import pytest

from bench import harness, tracing
from bench.tests import tiny

SEED = 2 ** 33 + 11
SPANS = ("engine.step", "engine.admit", "engine.prefill", "engine.headroom",
         "engine.dispatch", "engine.harvest", "engine.fetch")
#: the blocking reads of one decoding step of the tiny cell (paged,
#: chunked prefill, one split, numerics guard on), in order: headroom
#: reads the positions; harvest the tokens, emit mask, finite guard,
#: done and truncation masks
DECODE_READS = ["pos", "toks", "emits", "finite", "done", "trunc"]


def _fetch_whats(log_dir: Path) -> dict:
    """``{start_ns: what}`` of the ``engine.fetch`` spans (the
    reduction in ``tracing.load`` keeps names, not their arguments)."""
    from jax.profiler import ProfileData
    pb, = Path(log_dir).rglob("*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == "engine.fetch":
                        out[int(e.start_ns)] = dict(e.stats)["what"]
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A traced serve of the tiny cell: the trace record and the
    ``what`` of each read."""
    log_dir = tmp_path_factory.mktemp("trace")
    c = tiny.cell()
    built = harness.build(c, SEED)
    eng = harness.make_engine(c, built, log=lambda s: None)
    reqs = harness.backlog(c, SEED, built.dims.vocab)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "TRACE_DIR", log_dir)
        harness.serve(eng, reqs, 2.0, True, log=lambda s: None)
    return tracing.load(log_dir), _fetch_whats(log_dir)


def _spans(tr, name):
    return sorted((s, s + d) for n, s, d in tr["host"] if n == name)


def _inside(span, outer) -> bool:
    return any(a <= span[0] and span[1] <= b for a, b in outer)


def test_every_span_appears(served):
    tr, _ = served
    names = {h[0] for h in tr["host"]}
    assert set(SPANS) <= names


def test_spans_nest_in_engine_and_bench_steps(served):
    tr, _ = served
    steps = _spans(tr, "engine.step")
    assert steps
    for name in SPANS[1:]:
        for span in _spans(tr, name):
            assert _inside(span, steps), (name, span)
    bench_steps = _spans(tr, "bench.step")
    assert all(_inside(s, bench_steps) for s in steps)


def _reads_by_step(tr, whats) -> list:
    """``[(n_dispatches, has_prefill, [what, ...])]`` per engine step."""
    out = []
    for s, e in _spans(tr, "engine.step"):
        def within(name):
            return [x for x in _spans(tr, name) if s <= x[0] < e]
        out.append((len(within("engine.dispatch")),
                    bool(within("engine.prefill")),
                    [whats[a] for a, _ in within("engine.fetch")]))
    return out


def test_decoding_steps_make_the_same_reads(served):
    # the tiny cell admits and prefills in most steps: a prefill chunk
    # adds no read, and a step that decodes nothing reads nothing
    tr, whats = served
    assert len(whats) == len(_spans(tr, "engine.fetch"))
    steps = _reads_by_step(tr, whats)
    assert any(n == 1 and pf for n, pf, _ in steps)
    for n, _, reads in steps:
        assert reads == (DECODE_READS if n == 1 else [])


def test_plain_decode_step_reads(tmp_path):
    # two long answers: after their prefills, steps only decode
    import jax
    import numpy as np
    from repro.serving import Request
    c = tiny.cell()
    built = harness.build(c, SEED)
    eng = harness.make_engine(c, built, log=lambda s: None)
    prompt = np.arange(1, 17, dtype=np.int32)
    reqs = [Request(rid=i, prompt=prompt + i, max_new_tokens=40)
            for i in range(2)]
    with tracing.capture(tmp_path):
        eng.start(reqs)
        busy = True
        while busy:
            with jax.profiler.TraceAnnotation("bench.step"):
                busy = eng.step()
    steps = _reads_by_step(tracing.load(tmp_path), _fetch_whats(tmp_path))
    plain = [reads for n, pf, reads in steps if n == 1 and not pf]
    assert len(plain) >= 3
    assert all(reads == DECODE_READS for reads in plain)


def test_traced_run_reports_span_metrics(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    res = harness.run(tiny.cell(), SEED, 3.0, True, time.perf_counter(),
                      log=lambda s: None, peaks_of="TPU v5 lite")
    assert res["correct"], res["checks"]
    m = res["metrics"]
    # steps with nothing to decode make no read, decode steps six
    assert 1 <= m["host_syncs_per_step"]["value"] <= len(DECODE_READS)
    assert m["sched_host_ms"]["value"] > 0
    # no device plane on the CPU: the idle split cannot be read
    assert not {"idle_fetch_frac", "idle_sched_frac"} & set(m)
