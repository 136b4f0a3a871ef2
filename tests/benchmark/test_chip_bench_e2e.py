"""The window arithmetic of the chip benchmark's end-to-end metrics."""

import numpy as np
import pytest

from benchmarks.chip import e2e

T0, T1 = 10.0, 20.0


def _recs():
    R = e2e.Record
    return [
        # started before the window, finished inside: 10 of its tokens count
        R(0, 100, 20, due=5.0, first=6.0, finish=15.0, generated=20,
          gen_at_start=10, gen_at_end=20),
        # due and finished inside: 11 tokens, first after 1 s
        R(1, 100, 11, due=12.0, first=13.0, finish=18.0, generated=11,
          gen_at_start=0, gen_at_end=11),
        # due inside, still running at the close: 4 tokens so far
        R(2, 100, 50, due=14.0, first=16.0, generated=4, gen_at_start=0,
          gen_at_end=4),
        # due inside, no first token by the close: censored at 20 - 17
        R(3, 100, 50, due=17.0, gen_at_start=0, gen_at_end=0),
        # refused: counts as attempted and failed, censored TTFT
        R(4, 100, 50, due=19.0, failed=True),
        # due after the window: not attempted here
        R(5, 100, 50, due=21.0),
    ]


def test_tokens_are_split_at_the_window_edge():
    assert e2e.out_tok_s(_recs(), T0, T1) == pytest.approx((10 + 11 + 4) / 10)


def test_ttft_counts_from_due_and_censors_at_the_close():
    assert sorted(e2e.ttft_s(_recs(), T0, T1)) == pytest.approx(
        sorted([1.0, 2.0, 3.0, 1.0]))


def test_tpot_over_requests_finished_in_the_window():
    assert sorted(e2e.tpot_ms(_recs(), T0, T1)) == pytest.approx(
        sorted([1e3 * 9 / 19, 1e3 * 5 / 10]))


def test_window_metrics():
    m = e2e.window_metrics(_recs(), T0, T1)
    assert m["attempted"] == 4 and m["failed"] == 1
    assert m["ttft_p95_s"] == pytest.approx(
        np.percentile([1.0, 2.0, 3.0, 1.0], 95))
    assert e2e.p95([]) is None
