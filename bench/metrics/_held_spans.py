"""The engine's ``engine.held_pairs=<pairs>/<steps>/<touched>`` host
spans in a traced run, for the readers of ``expert_pairs_per_step`` and
``expert_roofline`` (not a metric itself).

A program with a held-experts layer names, per engine step, the
(token, held expert) pairs its decode iterations computed over all
expert layers, how many decode iterations ran, and how many held
experts those pairs touched, summed over layers and iterations
(docs/SERVING.md §Tracing).  A program without them yields ``None``."""
from bench import tracing

PREFIX = "engine.held_pairs="


def totals(run):
    """(pairs, decode iterations, touched experts) summed over the spans
    that start inside the traced window; None without such spans."""
    if not run.trace:
        return None
    lo, hi = tracing.window(run.trace)
    got = [name[len(PREFIX):].split("/") for name, start, _ in
           run.trace["host"] if name.startswith(PREFIX) and lo <= start <= hi]
    if not got:
        return None
    return tuple(sum(int(g[i]) for g in got) for i in range(3))
