"""Operation and byte counts, and the readers built on them, against
values worked out by hand for one small shape."""
import pytest

from bench import cost
from bench.configs.dense_reference import Dims
from bench.harness import Delivery, Run, Window
from bench.metrics import paged_decode_roofline, paged_prefill_roofline, \
    step_mfu

# D=8, 4 query / 2 kv heads of 4, d_ff 16, vocab 10, 2 layers; ranks 2/2
D = Dims(d_model=8, n_heads=4, n_kv_heads=2, d_head=4, d_ff=16, vocab=10,
         n_layers=2, rope_theta=1e4, rms_eps=1e-5)
RK = RV = 2
PEAKS = {"bf16_flops_per_s": 1e5, "hbm_bytes_per_s": 1e6}
KERNEL = "closed_call.1 custom-call bf16[1,4,1,128] tpu_custom_call"


def test_decode_attention_counts():
    # flops 2 * 4 heads * (2 + 2) * 5; bytes 2 * (2 kv * 4 * 5 + 4 * 4)
    assert cost.decode_attn(D, 5, RK, RV) == (160, 112)


def test_prefill_attention_counts():
    # 2 tokens at positions 3, 4 attend 4 + 5 = 9 entries
    assert cost.prefill_attn(D, 3, 2, RK, RV) == (288, 144)


def test_token_flops():
    # per layer: qkv 2*8*(4+2+2)*4 = 512, k/v factors 2*2*4*4 = 64,
    # B_q 2*4*4*2 = 64, C_v 2*4*2*8 = 128, MLP 6*8*16 = 768, attention
    # 2*4*4*ctx = 32 ctx; two layers and a head of 2*8*10 = 160
    assert cost.token_flops(D, 5, RK, RV) == 2 * (1536 + 32 * 5) + 160
    assert cost.token_flops(D, 5, RK, RV, head=False) == 2 * (1536 + 160)
    assert cost.prompt_flops(D, 3, RK, RV) == 3 * 3072 + 64 * 6 + 160


def test_chunks_and_contexts():
    assert cost.chunks(1100, 512) == [(0, 512), (512, 512), (1024, 76)]
    got = [Delivery(1, 0.0, 0, 3, 2, 5, False, None),
           Delivery(1, 0.0, 1, 2, 7, 5, True, None)]
    # 3 forwards at 5+2+1.. and 1 (the last token feeds none) at 5+7+1
    assert list(cost.decode_contexts(got)) == [8, 9, 10, 13]


def _run():
    trace = {"window": [0, 10 ** 9], "host": [], "devices": [{
        "name": "/device:TPU:0",
        "ops": [[KERNEL, 10 ** 8, 5 * 10 ** 7],
                [KERNEL, 3 * 10 ** 8, 5 * 10 ** 7],
                [KERNEL, 6 * 10 ** 8, 10 ** 7],
                ["fusion.1 fusion f32[8]", 7 * 10 ** 8, 10 ** 8]],
        "modules": [["jit__decode_chunk_impl(1)", 10 ** 8, 3 * 10 ** 8],
                    ["jit__prefill_chunk_impl(2)", 6 * 10 ** 8, 10 ** 7]]}]}
    w = Window(t_open=0.0, t_close=1.0, trace_span=(2, 3), deliveries=[
        Delivery(1, 0.1, 9, 8, 0, 50, False, None),    # before the trace
        Delivery(2, 0.2, 0, 3, 2, 5, False, 0.1),
        Delivery(3, 0.3, 1, 1, 0, 3, False, None)])     # first: prefill
    return Run(window=w, setup_s=0.0, memory_peak_bytes=0,
               peak_used_pages=0, n_pages=1, dims=D, ranks=(RK, RV),
               prefill_chunk=2, peaks=PEAKS, trace=trace)


def test_step_mfu():
    # decode forwards at 8, 9, 10 (request 0) and 4 (request 1), each
    # 3232 + 64 ctx; request 1's 3-token prompt 9760; over 1 s of a
    # 1e5 FLOP/s peak
    flops = sum(3232 + 64 * c for c in (8, 9, 10, 4)) + 9760
    assert step_mfu.read(_run()) == pytest.approx(100 * flops / 1e5)


def test_paged_decode_roofline():
    # 2 layers x sum over contexts 8, 9, 10, 4: flops 32 c -> 1984,
    # bytes 2 (8 c + 16) -> 1248; compute-bound: 0.01984 s of 0.1 s
    assert paged_decode_roofline.read(_run()) == pytest.approx(19.84)


def test_paged_prefill_roofline():
    # request 1's prompt of 3 in chunks (0, 2), (2, 1): flops
    # 2*4*4*(3 + 3) = 192, bytes 2*(2*4*2 + 2*4*4) + 2*(2*4*3 + 4*4)
    # = 176; times 2 layers over 0.01 s of kernel time
    want = 100 * max(2 * 192 / 1e5, 2 * 176 / 1e6) / 0.01
    assert paged_prefill_roofline.read(_run()) == pytest.approx(want)


def test_readers_without_their_kernel_report_nothing():
    run = _run()
    run.trace["devices"][0]["modules"] = []
    assert paged_decode_roofline.read(run) is None
    assert paged_prefill_roofline.read(run) is None
