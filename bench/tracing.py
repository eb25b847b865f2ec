"""Profiler traces: capture, reduce to a small plain record, read.

``capture`` wraps a stretch of the run in ``jax.profiler`` tracing.
``load`` reads the ``.xplane.pb`` it wrote into a plain dict (JSON-able,
so a small one is kept as test data):

    {"window": [start_ns, end_ns] | None,       # the "bench.window" span
     "devices": [{"name", "ops": [[label, start_ns, dur_ns], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}, ...],
     "host": [[name, start_ns, dur_ns], ...]}    # host-thread spans

Device planes are ``/device:TPU:<n>``; their "XLA Ops" line holds the
operations, nested (a ``while`` holds its body's operations), and the
"XLA Modules" line the compiled programs.  An operation's event is named
by its whole HLO text; ``label`` keeps its name, opcode, result shape and
custom-call target.  The reductions below clip every interval to the
window.
"""
from __future__ import annotations

import bisect
import contextlib
import re
import shutil
from pathlib import Path

#: host span that marks the traced window
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_SKIP_HOST = ("ThreadpoolListener",)
_HLO = re.compile(r"^%?([\w.\-]+) = ")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
LABEL_MAX = 120
#: gaps shorter than this sit between back-to-back operations and are
#: summed under one label instead of being looked up among host spans
SHORT_GAP_NS = 10_000
SHORT_GAP = "(between operations, under 10 us)"


def label(text: str) -> str:
    """``name opcode shape [target]`` of an operation's HLO text, e.g.
    ``closed_call.17 custom-call bf16[1,32,1,128] tpu_custom_call``."""
    m = _HLO.match(text)
    if not m:
        return text[:LABEL_MAX]
    rest = text[m.end():]
    if rest.startswith("("):                     # a tuple result
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "(tuple)", rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{")[0]
    op = re.match(r"[\w\-]+", rest)
    tgt = _TARGET.search(text)
    out = " ".join(x for x in (m.group(1), op.group(0) if op else "", shape,
                               tgt.group(1) if tgt else "") if x)
    return out[:LABEL_MAX]


@contextlib.contextmanager
def capture(log_dir: Path):
    """Trace the body; the host span ``bench.window`` marks its extent."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(str(log_dir))
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: Path) -> dict:
    """The newest trace under ``log_dir`` as a plain record."""
    from jax.profiler import ProfileData
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    devices, host = [], []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            rec = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    rec["ops"].extend(
                        [label(e.name), int(e.start_ns), int(e.duration_ns)]
                        for e in line.events)
                elif line.name == "XLA Modules":
                    rec["modules"].extend(
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events)
            devices.append(rec)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.duration_ns > 0
                            and not e.name.startswith(_SKIP_HOST))
    spans = [h for h in host if h[0] == WINDOW_SPAN]
    window = ([spans[0][1], spans[0][1] + spans[0][2]] if spans else None)
    return {"window": window, "devices": devices, "host": host}


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def window(tr: dict):
    """(start_ns, end_ns) of the traced window; without the span, the
    extent of the device operations."""
    if tr.get("window"):
        return tuple(tr["window"])
    ops = [o for d in tr["devices"] for o in d["ops"]]
    if not ops:
        return None
    return (min(o[1] for o in ops), max(o[1] + o[2] for o in ops))


def _clip(start, dur, lo, hi):
    return max(start, lo), min(start + dur, hi)


def merged(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(tr: dict, dev: dict) -> int:
    """Nanoseconds of the window in which an operation ran on ``dev``."""
    lo, hi = window(tr)
    return sum(e - s for s, e in merged(
        _clip(o[1], o[2], lo, hi) for o in dev["ops"]))


def _module_spans(dev: dict, pattern: str) -> list:
    return sorted((m[1], m[1] + m[2]) for m in dev["modules"]
                  if pattern in m[0])


def op_ns(tr: dict, pattern: str, module: str = "") -> tuple:
    """(device nanoseconds, count) of operations whose label holds
    ``pattern`` (and, with ``module``, that run inside a compiled program
    whose name holds it), summed over devices, inside the window."""
    lo, hi = window(tr)
    total = count = 0
    for d in tr["devices"]:
        spans = _module_spans(d, module) if module else None
        for o in d["ops"]:
            if pattern not in o[0]:
                continue
            if spans is not None:
                i = bisect.bisect_right(spans, (o[1], float("inf"))) - 1
                if i < 0 or o[1] >= spans[i][1]:
                    continue
            s, e = _clip(o[1], o[2], lo, hi)
            if e > s:
                total += e - s
                count += 1
    return total, count


def module_ns(tr: dict, pattern: str) -> tuple:
    """(device nanoseconds, count) of compiled programs whose name holds
    ``pattern``, summed over devices, inside the window."""
    lo, hi = window(tr)
    total = count = 0
    for d in tr["devices"]:
        for name, st, du in d["modules"]:
            if pattern in name:
                s, e = _clip(st, du, lo, hi)
                if e > s:
                    total += e - s
                    count += 1
    return total, count


def _self_ns(ops, lo, hi) -> list:
    """[(label, self nanoseconds)]: each clipped operation's time less
    that of the operations nested directly inside it."""
    iv = sorted(((max(o[1], lo), min(o[1] + o[2], hi), o[0]) for o in ops
                 if o[1] < hi and o[1] + o[2] > lo),
                key=lambda x: (x[0], -x[1]))
    own = [e - s for s, e, _ in iv]
    stack = []                                  # (end, index) of parents
    for i, (s, e, _) in enumerate(iv):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= e - s
        stack.append((e, i))
    return [(iv[i][2], own[i]) for i in range(len(iv)) if own[i] > 0]


def top_ops(tr: dict, n: int = 10) -> list:
    """[[label, seconds], ...] of the operations that took most device
    time of their own (less what runs nested inside them) in the window,
    averaged over devices, longest first."""
    lo, hi = window(tr)
    by: dict = {}
    for d in tr["devices"]:
        for name, ns in _self_ns(d["ops"], lo, hi):
            by[name] = by.get(name, 0) + ns
    nd = max(1, len(tr["devices"]))
    return [[k, v / nd / 1e9] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: dict, n: int = 10) -> list:
    """[[host activity, seconds], ...]: the device's idle time in the
    window (first device), summed by what the host was doing, most
    first.  A gap is named after the shortest host span that covers its
    middle, so the innermost activity names it."""
    lo, hi = window(tr)
    if not tr["devices"]:
        return []
    busy = merged(_clip(o[1], o[2], lo, hi) for o in tr["devices"][0]["ops"])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = sorted((h for h in tr["host"] if h[0] != WINDOW_SPAN),
                   key=lambda h: h[2])
    by: dict = {}
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        if e - s < SHORT_GAP_NS:
            label = SHORT_GAP
        else:
            mid = (s + e) / 2
            label = next((h[0] for h in spans
                          if h[1] <= mid <= h[1] + h[2]), "(no host span)")
        by[label] = by.get(label, 0) + e - s
    return [[k, v / 1e9] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def trimmed(tr: dict, max_ops: int = 400) -> dict:
    """A small copy for test data: the first ``max_ops`` operations of
    each device and the spans that overlap them."""
    devs = []
    for d in tr["devices"]:
        ops = sorted(d["ops"], key=lambda o: o[1])[:max_ops]
        end = max((o[1] + o[2] for o in ops), default=0)
        devs.append({"name": d["name"], "ops": ops,
                     "modules": [m for m in d["modules"] if m[1] < end]})
    start = min((o[1] for d in devs for o in d["ops"]), default=0)
    end = max((o[1] + o[2] for d in devs for o in d["ops"]), default=0)
    host = [h for h in tr["host"] if h[1] < end and h[1] + h[2] > start
            and h[0] != WINDOW_SPAN]
    return {"window": [start, end], "devices": devs, "host": host}
