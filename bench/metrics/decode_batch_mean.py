"""Scheduler: requests that received tokens per step, averaged over the
window's steps that delivered any (host counts)."""


def read(run):
    n = [k for _, _, k in run.window.steps if k]
    return sum(n) / len(n) if n else None
