"""``mimo_cost``'s counts against values worked out by hand at the tiny
MiMo widths, the MiMo readers on a hand-made trace (and ``None`` where
their spans are missing), and a CPU rehearsal of the tiny MiMo cell
through the harness: sound runs pass the check, the float8 control
fails it, and ``bench/routed_limits.py`` reads what the check reads."""
import importlib
import time

import pytest

from bench import harness
from bench.configs import mimo_cost
from bench.configs import mimo_reference as ref
from bench.harness import Delivery, Run, Window
from bench import routed_limits
from bench.metrics import (expert_pairs_per_step, expert_roofline,
                           mimo_full_decode_roofline,
                           mimo_full_prefill_roofline, mimo_step_mfu,
                           window_attn_roofline)
from bench.tests import tiny_mimo

# D 64, 4 query heads, KV heads 1 (full) and 2 (window), q/k 24, v 16,
# window 16, dense MLP 96, experts 32 wide, 4 of 16 held, top-4, vocab
# 256; layers F S S S S F; ranks 2/2
D = ref.Dims.of(tiny_mimo.conf(n_layers=6))
RK = RV = 2
PEAKS = {"bf16_flops_per_s": 1e5, "hbm_bytes_per_s": 1e6}
WIN = "kq_window_paged_attention.3 custom-call bf16[2,2,2,2] tpu_custom_call"
RD = "ragged-dot-none custom-call bf16[8,32] tpu_custom_call"
FULL = "kq_decode_paged_attention.6 custom-call bf16[2,1,4,2] tpu_custom_call"
PRE = "kq_prefill_paged_attention.6 custom-call bf16[1,4,32,2] tpu_custom_call"


def test_attention_counts():
    # window at ctx 20 attends 16: flops 2*4*(2+2)*16, bytes
    # 2*(2 kv*4*16 + 4*4); full attends 20 with 1 kv head
    assert mimo_cost.decode_attn(D, "window", 20, RK, RV) == (512, 288)
    assert mimo_cost.decode_attn(D, "full", 20, RK, RV) == (640, 192)
    assert mimo_cost.decode_attn(D, "window", 5, RK, RV) == (160, 2 * 56)


def test_expert_counts():
    # 3 held pairs of 6*64*32 flops touching 2 experts: their 3*64*32
    # weights once each, and the pairs' 64-wide activations in and out
    assert mimo_cost.expert_work(D, 3, 2) == (
        3 * 6 * 64 * 32, 2 * (2 * 3 * 64 * 32 + 2 * 3 * 64))
    assert mimo_cost.held_pairs_per_token(D) == 1.0     # 4 * 4 / 16


def test_token_flops():
    # full layer: q/k/v 2*64*(96 + 40) + factors 2*(48 + 32) + B_q
    # 2*4*48 + C_v 2*4*2*64 + attention 2*4*4*20 = 19616; window (2 kv
    # heads, ctx 16): 22528 + 320 + 384 + 1024 + 512 = 24768; layer 0's
    # MLP 6*64*96; an expert layer's router 2*64*16 plus 1 expected
    # held pair of 6*64*32; head 2*64*256
    layers = (19616 + 36864) + 4 * (24768 + 14336) + (19616 + 14336)
    assert mimo_cost.token_flops(D, 20, RK, RV, head=False) == layers
    assert mimo_cost.token_flops(D, 20, RK, RV) == layers + 32768


HELD = [["engine.held_pairs=12/8/5", 10 ** 8, 1000],
        ["engine.held_pairs=3/2/2", 5 * 10 ** 8, 1000],
        ["engine.held_pairs=99/1/9", 2 * 10 ** 9, 1000]]   # after it


def _run(host=None):
    ms = 10 ** 6
    trace = {"window": [0, 1000 * ms], "host": host or [], "devices": [{
        "name": "/device:TPU:0",
        "ops": [[WIN, 100 * ms, 10 * ms], [RD, 120 * ms, 50 * ms],
                [FULL, 175 * ms, 20 * ms], [WIN, 600 * ms, 10 * ms],
                [PRE, 605 * ms, 10 * ms]],
        "modules": [["jit__decode_chunk_impl(1)", 100 * ms, 100 * ms],
                    ["jit__prefill_chunk_impl(2)", 600 * ms, 20 * ms]]}]}
    w = Window(t_open=0.0, t_close=1.0, trace_span=(2, 3), deliveries=[
        Delivery(2, 0.2, 0, 3, 2, 5, False, 0.1),
        Delivery(3, 0.3, 1, 1, 0, 3, False, None)])     # first: prefill
    return Run(window=w, setup_s=0.0, memory_peak_bytes=0,
               peak_used_pages=0, n_pages=1, dims=D, ranks=(RK, RV),
               prefill_chunk=32, peaks=PEAKS, trace=trace)


def test_mimo_step_mfu():
    # decode forwards at 8, 9, 10 (request 0) and 4 (request 1, whose
    # 3-token prompt is counted whole) over 1 s of a 1e5 FLOP/s peak
    flops = sum(mimo_cost.token_flops(D, c, RK, RV) for c in (8, 9, 10, 4))
    flops += mimo_cost.prompt_flops(D, 3, RK, RV)
    assert mimo_step_mfu.read(_run()) == pytest.approx(100 * flops / 1e5)


def test_window_attn_roofline():
    # 4 window layers over contexts 8, 9, 10, 4 (all under the window):
    # flops 32 c, bytes 2*(8 c + 16); the decode program's one kernel
    # call, 10 ms (the prefill program's is not counted)
    ctx = (8, 9, 10, 4)
    flops, nbytes = 4 * sum(32 * c for c in ctx), \
        4 * sum(2 * (8 * c + 16) for c in ctx)
    want = 100 * max(flops / 1e5, nbytes / 1e6) / 0.01
    assert window_attn_roofline.read(_run()) == pytest.approx(want)


def test_mimo_full_decode_roofline():
    # the 2 full layers (0 and 5, one KV head) over contexts 8, 9, 10,
    # 4: flops 32 c, bytes 2*(4 c + 16); the decode program's 20 ms of
    # kq_decode_paged_attention (not the window kernel's)
    ctx = (8, 9, 10, 4)
    flops, nbytes = 2 * sum(32 * c for c in ctx), \
        2 * sum(2 * (4 * c + 16) for c in ctx)
    want = 100 * max(flops / 1e5, nbytes / 1e6) / 0.02
    assert mimo_full_decode_roofline.read(_run()) == pytest.approx(want)


def test_mimo_full_prefill_roofline():
    # request 1's 3-token prompt in one chunk: 6 causal pairs, flops
    # 2*4*4*6, bytes 2*(1 kv*4*3 + 3*4*4), in 2 full layers, against the
    # prefill program's 10 ms of kq_prefill_paged_attention
    flops, nbytes = 2 * 192, 2 * 2 * (12 + 48)
    assert mimo_cost.prefill_attn(D, 0, 3, RK, RV) == (192, 120)
    want = 100 * max(flops / 1e5, nbytes / 1e6) / 0.01
    assert mimo_full_prefill_roofline.read(_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "device_idle_frac", "host_syncs_per_step", "decode_step_ms",
    "prefill_busy_frac", "decode_batch_mean", "pool_peak_frac",
    "idle_fetch_frac", "idle_sched_frac", "sched_host_ms"])
def test_model_free_readers_have_mimo_entries(name):
    """Each ``mimo_<name>`` entry reads with ``<name>``'s reader, and
    reads the same on a MiMo run as that reader."""
    twin = harness.reader(f"mimo_{name}")
    assert importlib.import_module(f"bench.metrics.{name}").read is twin
    assert twin(_run(HELD)) == harness.reader(name)(_run(HELD))


def test_expert_roofline():
    # the spans inside the window: 15 pairs touching 7 (expert, call)
    # weight sets, against the decode program's 50 ms of ragged-dot
    f, b = 15 * 6 * 64 * 32, 2 * (7 * 3 * 64 * 32 + 2 * 15 * 64)
    want = 100 * max(f / 1e5, b / 1e6) / 0.05
    assert expert_roofline.read(_run(HELD)) == pytest.approx(want)


def test_expert_pairs_per_step():
    # 15 pairs over 10 decode iterations and 5 expert layers
    assert expert_pairs_per_step.read(_run(HELD)) == pytest.approx(
        15 / 10 / 5)


def test_readers_without_their_spans_report_nothing():
    run = _run(HELD)
    run.trace["devices"][0]["modules"] = []
    assert window_attn_roofline.read(run) is None
    assert expert_roofline.read(run) is None        # no decode program
    assert mimo_full_decode_roofline.read(run) is None
    assert mimo_full_prefill_roofline.read(run) is None
    assert expert_pairs_per_step.read(_run()) is None   # no spans
    assert expert_roofline.read(_run()) is None
    # a dense configuration's run reads nothing from them either
    from bench.tests.test_cost import _run as dense_run
    dense = dense_run()
    for m in (mimo_step_mfu, window_attn_roofline, expert_roofline,
              expert_pairs_per_step, mimo_full_decode_roofline,
              mimo_full_prefill_roofline):
        assert m.read(dense) is None


SEED = 2 ** 33 + 7


def test_traced_rehearsal_is_correct_and_counts_pairs():
    res = harness.run(tiny_mimo.cell(), SEED, 2.0, True,
                      time.perf_counter(), log=lambda s: None,
                      peaks_of="TPU v5 lite")
    assert res["correct"], res["checks"]
    # no device plane on the CPU: the host spans and counters and the
    # window-timed MFU read, the device-op readers do not
    assert {"expert_pairs_per_step", "mimo_step_mfu",
            "mimo_decode_batch_mean", "mimo_pool_peak_frac",
            "mimo_sched_host_ms", "mimo_host_syncs_per_step"} == set(
                res["metrics"])
    assert res["metrics"]["expert_pairs_per_step"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 3])
def test_float8_control_fails(seed):
    c = tiny_mimo.cell()
    built = harness.build(c, seed)
    eng = harness.make_engine(c, built, log=lambda s: None)
    reqs = harness.backlog(c, seed, built.dims.vocab)
    harness.warm_up(eng, reqs, built.dims.vocab)
    win = harness.serve(eng, reqs, 0.5, False, log=lambda s: None)
    sound = harness.check(built, c, reqs, win, seed)
    assert harness.checks_pass(sound)
    control = harness.check(built, c, reqs, win, seed, control="fp8")
    assert control["logit_gap"]["value"] > control["logit_gap"]["limit"]
    # the limits script reads, position by position, what the check reads
    got = routed_limits.summary(
        routed_limits.readings(built, c, reqs, win, seed), ref.ROUTE_MARGIN)
    assert got["tokens"] == sound["tokens_checked"]["value"]
    assert got["gap"] == pytest.approx(sound["logit_gap"]["value"])
    assert got["control"] == pytest.approx(control["logit_gap"]["value"])
    assert got["gap_unscored_too"] >= got["gap"]


def test_router_margin():
    """The margin is the least distance of a held expert's score plus
    bias from the boundary of the chosen (by brute force over the sorted
    keys), and gaps are not scored under ``route_margin``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    d = ref.Dims.of(tiny_mimo.conf())
    w = jax.jit(lambda k: ref.init_weights(d, k))(jax.random.PRNGKey(4))
    i = d.moe_layers[0]
    lw = {k: w[f"{k}.{i}"] for k in ("router", "bias", "wi_e", "wg_e",
                                     "wo_e")}
    lw["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (16,))
    h = jax.random.normal(jax.random.PRNGKey(6), (40, d.d_model))
    with jax.default_matmul_precision("highest"):
        _, margin = ref._experts(d, h, lw, "f32")
    keys = np.asarray(jax.nn.sigmoid(h @ lw["router"].astype(jnp.float32))
                      + lw["bias"])
    for row, m in zip(keys, np.asarray(margin)):
        srt = np.sort(row)[::-1]
        last_in, first_out = srt[d.top_k - 1], srt[d.top_k]
        want = min(k - first_out if k >= last_in else last_in - k
                   for k in row[:d.n_held])
        assert m == pytest.approx(want, abs=1e-6)
    gaps = jnp.arange(40.0)
    assert (np.asarray(ref._scored(margin, gaps, 0.0)) == np.arange(40)).all()
    cut = float(np.median(margin))
    kept = np.asarray(ref._scored(margin, gaps, cut))
    assert (kept == np.where(np.asarray(margin) >= cut, np.arange(40), 0)
            ).all()
