"""One generator for every traffic mix: a mix is a data file of
parameters under ``bench/traffic/<name>.json``.

An ``offline_batch`` mix is a backlog of requests handed to the engine
at once.  Prompt and output lengths follow clipped lognormal laws, drawn
by strata: the backlog is cut into blocks of ``strata`` requests, and
every block holds the same ``strata`` requests, in the same order.
Request ``j`` of a block takes the prompt law's quantile at
(i + 1/2) / strata for the stratum ``i`` that ranks ``frac(j a1)``
among the block, and the output law's for the one that ranks
``frac(j a2)``: a two-dimensional low-discrepancy sequence (``R2``), so
every run of consecutive requests spreads over both laws and their
pairings, and a window that serves a part of a block sees the mix, not
one end of it.  The seed draws only the token ids.  So every seed asks
the same work in the same order.

Keys of a mix file (``source``, ``derivation``, ``assumed`` and
``reduced`` say where the numbers come from; the generator reads the
rest):

    kind        "offline_batch"
    backlog     requests in the backlog
    strata      requests per block (the backlog is a multiple of it)
    prompt      {"median", "sigma", "min", "max"}: tokens per prompt
    output      {"median", "sigma", "min", "max"}: tokens per answer
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

DIR = Path(__file__).resolve().parent / "traffic"
#: SeedSequence stream of the traffic (weights and calibration use others)
STREAM = 2
#: the R2 sequence's steps: 1/g and 1/g**2 for g the plastic number
_G = 1.32471795724474602596
R2 = (1 / _G, 1 / _G ** 2)


def load(name: str) -> dict:
    return json.loads((DIR / f"{name}.json").read_text())


def strata_lengths(law: dict, n: int) -> np.ndarray:
    """The ``n`` quantiles of a clipped lognormal at (i + 1/2) / n."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = [law["median"] * math.exp(law["sigma"] * zi) for zi in z]
    return np.clip(np.rint(x), law["min"], law["max"]).astype(np.int64)


def order(n: int, step: float) -> np.ndarray:
    """For j < n, the rank of ``frac((j + 1) step)`` among them: a
    permutation of the strata whose every prefix is spread evenly."""
    return np.argsort(np.argsort([((j + 1) * step) % 1.0
                                  for j in range(n)]))


def block(mix: dict) -> list:
    """One block's ``(prompt length, output length)`` pairs, in order."""
    n = mix["strata"]
    p_len = strata_lengths(mix["prompt"], n)[order(n, R2[0])]
    o_len = strata_lengths(mix["output"], n)[order(n, R2[1])]
    return [(int(p), int(o)) for p, o in zip(p_len, o_len)]


def generate(mix: dict, seed: int, vocab: int) -> list:
    """The backlog as ``(prompt token ids, output tokens)`` pairs."""
    if mix["kind"] != "offline_batch":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    n, backlog = mix["strata"], mix["backlog"]
    if backlog % n:
        raise ValueError("backlog must be a multiple of strata")
    rng = np.random.default_rng([seed, STREAM])
    pairs = block(mix) * (backlog // n)
    return [(rng.integers(0, vocab, p).astype(np.int32), o)
            for p, o in pairs]
