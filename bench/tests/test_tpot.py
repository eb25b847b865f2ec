"""The end-to-end readers on a hand-made delivery log: dispatches that
bring several tokens, and one stalled step."""
import pytest

from bench.harness import Delivery, Run, Window
from bench.metrics import output_tok_per_s, tpot_p95_ms


def _run(deliveries, t_open=0.0, t_close=1.0):
    w = Window(t_open=t_open, t_close=t_close, deliveries=deliveries)
    return Run(window=w, setup_s=1.0, memory_peak_bytes=0,
               peak_used_pages=0, n_pages=1, dims=None, ranks=(0, 0),
               prefill_chunk=1, peaks={})


def _log():
    """Request 0: first delivery at 0.1 s (sets its clock), then 8
    tokens every 0.1 s, then a stall of 1.0 s before 8 more.  Request 1:
    its first delivery only.  So 8 x 4 tokens at 12.5 ms and 8 at
    125 ms."""
    d = [Delivery(1, 0.1, 0, 8, 0, 10, False, None),
         Delivery(1, 0.1, 1, 3, 0, 10, False, None)]
    t, before = 0.1, 8
    for _ in range(4):
        t += 0.1
        d.append(Delivery(0, t, 0, 8, before, 10, False, 0.1 / 8))
        before += 8
    d.append(Delivery(0, t + 1.0, 0, 8, before, 10, True, 1.0 / 8))
    return d


def test_tpot_counts_every_token_and_the_stall():
    # 40 tokens with a gap: 32 at 12.5 ms, 8 at 125 ms.  The 95th
    # percentile (numpy's linear rule) falls in the stalled dispatch.
    assert tpot_p95_ms.read(_run(_log())) == pytest.approx(125.0)


def test_tpot_without_the_stall():
    log = _log()[:-1]
    assert tpot_p95_ms.read(_run(log)) == pytest.approx(12.5)


def test_tpot_first_deliveries_alone_give_nothing():
    assert tpot_p95_ms.read(_run(_log()[:2])) is None


def test_output_rate_counts_all_tokens_over_the_window():
    # 8 + 3 + 4 * 8 + 8 = 51 tokens over a 2.0 s window
    assert output_tok_per_s.read(_run(_log(), 0.0, 2.0)) == \
        pytest.approx(25.5)
