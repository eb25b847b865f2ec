"""The engine's host spans in a traced run, for the readers of
``idle_fetch_frac``, ``idle_sched_frac``, ``sched_host_ms`` and
``host_syncs_per_step`` (not a metric itself).

The program opens ``engine.step`` around each scheduling iteration and
``engine.fetch`` around each blocking device-to-host read inside it
(``repro.serving.engine``); ``run.trace["host"]`` holds them on the
device trace's clock.  A program without them yields ``None``."""
import bisect

from bench import tracing

STEP, FETCH = "engine.step", "engine.fetch"


def _ns(intervals) -> int:
    """Nanoseconds covered by the union of ``intervals``."""
    return sum(e - s for s, e in tracing.merged(intervals))


def _clipped(tr: dict, name: str) -> list:
    lo, hi = tracing.window(tr)
    return [tracing._clip(s, d, lo, hi) for n, s, d in tr["host"]
            if n == name]


def idle_frac(tr: dict, inside: str, outside: str = ""):
    """Share of the window in which device 0 ran no operation while a
    span named ``inside`` was open and none named ``outside`` was; None
    without ``inside`` spans or without a device plane.

    Idle time under a set of spans U is |busy | U| - |busy|, so idle
    time under ``inside`` but not ``outside`` is |busy | in | out| -
    |busy | out|."""
    if not tr["devices"]:
        return None
    spans_in = _clipped(tr, inside)
    if not spans_in:
        return None
    lo, hi = tracing.window(tr)
    busy = [tracing._clip(o[1], o[2], lo, hi)
            for o in tr["devices"][0]["ops"]]
    out = _clipped(tr, outside) if outside else []
    return (_ns(busy + spans_in + out) - _ns(busy + out)) / (hi - lo)


def steps(tr: dict):
    """``[(step_ns, fetch_ns, n_fetches)]`` for each ``engine.step``
    span wholly inside the window: its length, the part of it under its
    ``engine.fetch`` spans, and how many of those it holds; None
    without such steps."""
    lo, hi = tracing.window(tr)
    whole = sorted((s, s + d) for n, s, d in tr["host"]
                   if n == STEP and lo <= s and s + d <= hi)
    if not whole:
        return None
    fetches = sorted((s, s + d) for n, s, d in tr["host"] if n == FETCH)
    starts = [f[0] for f in fetches]
    out = []
    for s, e in whole:
        inner = fetches[bisect.bisect_left(starts, s):
                        bisect.bisect_left(starts, e)]
        inner = [(a, min(b, e)) for a, b in inner]
        out.append((e - s, _ns(inner), len(inner)))
    return out
