"""The reduction from a profiler trace to device busy time, kernel and
program time, top operations and idle gaps named by host activity."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import tracing

US = 1000                                    # ns per microsecond


def _trace():
    """1 ms window; b runs nested in a, the call alone, d runs past the
    window."""
    return {
        "window": [0, 1000 * US],
        "devices": [{
            "name": "/device:TPU:0",
            "ops": [["a", 0, 150 * US],
                    ["b", 50 * US, 50 * US],
                    ["call.7 custom-call tpu_custom_call", 300 * US,
                     100 * US],
                    ["d", 950 * US, 100 * US]],
            "modules": [["jit__decode_chunk_impl(3)", 0, 400 * US],
                        ["jit__prefill_chunk_impl(9)", 900 * US,
                         200 * US]]}],
        "host": [["bench.step", 0, 1000 * US],
                 ["bench.collect", 380 * US, 600 * US]]}


def test_busy_is_the_union_clipped_to_the_window():
    tr = _trace()
    # [0, 150] + [300, 400] + [950, 1000] us
    assert tracing.busy_ns(tr, tr["devices"][0]) == 300 * US


def test_ops_match_by_label_and_program():
    tr = _trace()
    assert tracing.op_ns(tr, "b") == (50 * US, 1)
    k = "tpu_custom_call"
    assert tracing.op_ns(tr, k, module="decode_chunk") == (100 * US, 1)
    assert tracing.op_ns(tr, k, module="prefill_chunk") == (0, 0)


def test_modules_by_name():
    tr = _trace()
    assert tracing.module_ns(tr, "decode_chunk") == (400 * US, 1)
    assert tracing.module_ns(tr, "prefill_chunk") == (100 * US, 1)


def test_top_ops_and_idle_gaps():
    tr = _trace()
    # a's own time leaves out b's, nested in it; d is clipped
    assert dict(tracing.top_ops(tr)) == {
        "a": pytest.approx(100e-6), "b": pytest.approx(50e-6),
        "call.7 custom-call tpu_custom_call": pytest.approx(100e-6),
        "d": pytest.approx(50e-6)}
    # [150, 300] us lies only under bench.step; [400, 950] us under the
    # shorter bench.collect, which names it
    assert tracing.idle_gaps(tr) == [["bench.collect", pytest.approx(550e-6)],
                                     ["bench.step", pytest.approx(150e-6)]]


def test_short_gaps_are_pooled():
    tr = _trace()
    tr["devices"][0]["ops"] = [["a", 0, 495 * US],
                               ["b", 500 * US, 500 * US]]
    assert tracing.idle_gaps(tr) == [[tracing.SHORT_GAP,
                                      pytest.approx(5e-6)]]


def test_nested_ops_count_their_own_time():
    tr = _trace()
    tr["devices"][0]["ops"] = [["while.1 while (tuple)", 0, 600 * US],
                               ["k", 100 * US, 200 * US],
                               ["j", 350 * US, 100 * US]]
    assert dict(tracing.top_ops(tr)) == {
        "while.1 while (tuple)": pytest.approx(300e-6),
        "k": pytest.approx(200e-6), "j": pytest.approx(100e-6)}
    assert tracing.busy_ns(tr, tr["devices"][0]) == 600 * US


@pytest.mark.parametrize("text, want", [
    ('%closed_call.17 = bf16[1,32,1,128]{3,2,1,0:T(2,128)(2,1)S(1)} '
     'custom-call(s32[1]{0:T(128)} %g.2), custom_call_target='
     '"tpu_custom_call", operand_layout_constraints={s32[1]{0}}',
     "closed_call.17 custom-call bf16[1,32,1,128] tpu_custom_call"),
    ('%while.22 = (s32[]{:T(128)}, f32[1,32064]{1,0:T(1,128)}) '
     'while((s32[]{:T(128)}, f32[1,32064]{1,0}) %tuple.128), '
     'condition=%c, body=%b', "while.22 while (tuple)"),
    ("%copy.24 = bf16[32,49,32,64,48]{4,3,2,1,0:T(8,128)(2,1)} "
     "copy(bf16[32,49,32,64,48]{3,4,2,1,0:T(8,128)(2,1)} %p)",
     "copy.24 copy bf16[32,49,32,64,48]"),
    ("not hlo", "not hlo")])
def test_labels(text, want):
    assert tracing.label(text) == want


def test_window_falls_back_to_the_device_extent():
    tr = _trace()
    tr["window"] = None
    assert tracing.window(tr) == (0, 1050 * US)


def test_capture_and_load_a_live_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tracing.capture(tmp_path):
        with jax.profiler.TraceAnnotation("test.span"):
            f(x).block_until_ready()
    tr = tracing.load(tmp_path)
    lo, hi = tracing.window(tr)
    assert hi > lo
    span = [h for h in tr["host"] if h[0] == "test.span"]
    assert span and lo <= span[0][1] <= span[0][1] + span[0][2] <= hi


# ---------------------------------------------------------------------------
# a recorded trace: the first 400 operations of a decode dispatch of
# phi-3 (bench/configs/phi-3-vision-4.2b-text.json) serving one long
# document at a time on one TPU v5e (bench/limits.py --dump-trace), labelled
# ---------------------------------------------------------------------------

RECORDED = Path(__file__).parent / "data" / "trace_v5e_longdoc.json"


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


def test_recorded_busy_agrees_with_a_microsecond_grid(recorded):
    dev = recorded["devices"][0]
    lo, hi = tracing.window(recorded)
    grid = np.zeros((hi - lo) // US + 2, bool)
    for _, s, d in dev["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[(a - lo) // US: -(-(b - lo) // US)] = True
    # each interval's edges round out by at most 1 us on the grid
    busy_us = tracing.busy_ns(recorded, dev) / US
    assert abs(grid.sum() - busy_us) <= 2 * len(dev["ops"])
    assert 0.9 * (hi - lo) / US < busy_us <= (hi - lo) / US


def test_recorded_own_times_add_up_to_busy(recorded):
    dev = recorded["devices"][0]
    own = sum(s for _, s in tracing.top_ops(recorded, n=10 ** 6))
    assert own == pytest.approx(tracing.busy_ns(recorded, dev) / 1e9)


def test_recorded_idle_and_busy_fill_the_window(recorded):
    lo, hi = tracing.window(recorded)
    idle = sum(s for _, s in tracing.idle_gaps(recorded))
    busy = tracing.busy_ns(recorded, recorded["devices"][0]) / 1e9
    assert idle + busy == pytest.approx((hi - lo) / 1e9)


def test_recorded_decode_kernel_is_found_in_its_program(recorded):
    # one Pallas call per layer and token step; all of this slice runs
    # inside the decode program
    ns, n = tracing.op_ns(recorded, "tpu_custom_call",
                          module="decode_chunk")
    assert n == 9 and ns > 0
    assert tracing.op_ns(recorded, "tpu_custom_call",
                         module="prefill_chunk") == (0, 0)
    top = tracing.top_ops(recorded, n=3)
    assert all(s > 0 for _, s in top)
