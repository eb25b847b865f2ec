"""A reduced-size rehearsal of a cell through the harness functions on
the CPU: the sound program passes the check, and the check fails for
the control (float8 in the program's place) and for faults planted
underneath the timed path."""
import time

import pytest

from bench import harness
from bench.tests import tiny

SEED = 2 ** 33 + 7


def _run(seconds=1.5, trace=False):
    return harness.run(tiny.cell(), SEED, seconds, trace,
                       time.perf_counter(), log=lambda s: None,
                       peaks_of="TPU v5 lite")


def test_sound_run_is_correct_and_reports_the_cell():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compiles_in_window"] == 0
    # on the CPU the device reports no memory: three of the four remain
    assert set(res["metrics"]) == {"output_tok_per_s", "tpot_p95_ms",
                                   "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_traced_run_reports_host_layer_metrics():
    res = _run(seconds=3.0, trace=True)
    assert res["correct"], res["checks"]
    # no device plane on the CPU: only the host counters can read
    assert {"decode_batch_mean", "pool_peak_frac"} <= set(res["metrics"])
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _served(seed):
    c = tiny.cell()
    built = harness.build(c, seed)
    eng = harness.make_engine(c, built, log=lambda s: None)
    reqs = harness.backlog(c, seed, built.dims.vocab)
    harness.warm_up(eng, reqs, built.dims.vocab)
    win = harness.serve(eng, reqs, 0.5, False, log=lambda s: None)
    return c, built, reqs, win


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_lower_precision_fails(seed):
    c, built, reqs, win = _served(seed)
    assert harness.checks_pass(harness.check(built, c, reqs, win, seed))
    control = harness.check(built, c, reqs, win, seed, control="fp8")
    assert not harness.checks_pass(control)
    assert control["logit_gap"]["value"] > control["logit_gap"]["limit"]


def _token_altered(mp):
    import repro.serving.engine as engine
    sample = engine.sample_token
    mp.setattr(engine, "sample_token",
               lambda lg, t, r: (sample(lg, t, r) + 1) % lg.shape[-1])


def _state_unchanged(mp):
    import repro.models.attention as attention
    mp.setattr(attention, "append_token", lambda pool, bt, pos, v: pool)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token_altered", "decode_state_unchanged"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]
