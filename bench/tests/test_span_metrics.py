"""The readers of the engine's host spans (``idle_fetch_frac``,
``idle_sched_frac``, ``sched_host_ms``, ``host_syncs_per_step``) on a
hand-made trace record with known answers."""
import types

import pytest

from bench import harness

US = 1000                                    # ns per microsecond
METRICS = ("idle_fetch_frac", "idle_sched_frac", "sched_host_ms",
           "host_syncs_per_step")


def _trace():
    """1 ms window; device 0 busy [0, 300] and [500, 800] us, so idle
    [300, 500] and [800, 1000].  Two engine steps lie wholly inside the
    window, [50, 450] with reads [100, 200] (device busy) and [350, 450]
    (idle), and [500, 950] with a read [850, 900] (idle); a third,
    [960, 1060], runs past the window's end with a read [970, 990]."""
    return {
        "window": [0, 1000 * US],
        "devices": [{"name": "/device:TPU:0",
                     "ops": [["a", 0, 300 * US], ["b", 500 * US, 300 * US]],
                     "modules": []}],
        "host": [["bench.step", 0, 1000 * US],
                 ["engine.step", 50 * US, 400 * US],
                 ["engine.fetch", 100 * US, 100 * US],
                 ["engine.fetch", 350 * US, 100 * US],
                 ["engine.step", 500 * US, 450 * US],
                 ["engine.fetch", 850 * US, 50 * US],
                 ["engine.step", 960 * US, 100 * US],
                 ["engine.fetch", 970 * US, 20 * US]]}


def _no_engine_spans():
    tr = _trace()
    tr["host"] = [h for h in tr["host"] if not h[0].startswith("engine.")]
    return tr


def _no_device():
    tr = _trace()
    tr["devices"] = []
    return tr


# idle under reads: [350, 450] + [850, 900] + [970, 990] = 170 us; idle
# in steps outside reads: [300, 350] + [800, 850] + [900, 950] + [960,
# 970] + [990, 1000] = 170 us; the two whole steps spend 400 - 200 and
# 450 - 50 us outside reads, and hold 3 reads
@pytest.mark.parametrize("trace, want", [
    (_trace, {"idle_fetch_frac": 0.17, "idle_sched_frac": 0.17,
              "sched_host_ms": 0.3, "host_syncs_per_step": 1.5}),
    (_no_engine_spans, dict.fromkeys(METRICS)),
    (_no_device, {"idle_fetch_frac": None, "idle_sched_frac": None,
                  "sched_host_ms": 0.3, "host_syncs_per_step": 1.5}),
], ids=["known", "no_engine_spans", "no_device_plane"])
@pytest.mark.parametrize("metric", METRICS)
def test_span_reader(metric, trace, want):
    got = harness.reader(metric)(types.SimpleNamespace(trace=trace()))
    if want[metric] is None:
        assert got is None
    else:
        assert got == pytest.approx(want[metric])


def test_idle_split_lies_within_device_idle():
    run = types.SimpleNamespace(trace=_trace(), trace_seconds=lambda: 1e-3)
    parts = [harness.reader(m)(run) for m in METRICS[:2]]
    assert sum(parts) <= harness.reader("device_idle_frac")(run)
