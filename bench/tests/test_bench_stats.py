"""Metric arithmetic: tails from due time, unfinished requests at their
lower bound, failures as missing, rates over the whole window."""

from __future__ import annotations

import pytest

from bench import stats
from bench.stats import RequestRecord as R


def test_ttft_counts_from_due_time_and_bounds_the_unfinished():
    w0, w1 = 10.0, 20.0
    recs = [
        R(0, due=9.0, submitted=9.0, prompt_len=8, max_new=4,
          tok_t=[9.5, 9.6]),                      # due before: not counted
        R(1, due=11.0, submitted=11.2, prompt_len=8, max_new=4,
          tok_t=[11.5, 11.6, 11.7]),              # 0.5 s from due
        R(2, due=12.0, submitted=12.0, prompt_len=8, max_new=4,
          tok_t=[]),                              # never served: 8 s
        R(3, due=13.0, submitted=13.0, prompt_len=8, max_new=4,
          tok_t=[21.0]),                          # first after w1: 7 s
        R(4, due=14.0, submitted=14.0, prompt_len=8, max_new=4,
          tok_t=[], failed=True),                 # failed: missing, 6 s
        R(5, due=20.0, submitted=20.0, prompt_len=8, max_new=4,
          tok_t=[20.1]),                          # due at w1: not counted
    ]
    assert sorted(stats.ttft_samples(recs, w0, w1)) == pytest.approx(
        [0.5, 6.0, 7.0, 8.0])


def test_itl_and_rate_over_the_whole_window():
    w0, w1 = 10.0, 20.0
    recs = [R(0, 9.0, 9.0, 8, 8, tok_t=[9.0, 9.5, 10.5, 11.0, 20.5]),
            R(1, 12.0, 12.0, 8, 8, tok_t=[12.0, 12.25])]
    assert sorted(stats.itl_samples(recs, w0, w1)) == pytest.approx(
        [0.25, 0.5, 1.0])
    assert stats.tokens_in(recs, w0, w1) == 4     # 10.5 11.0 12.0 12.25


def test_percentile_is_exact_and_queue_wait_bounded():
    assert stats.percentile(list(range(1, 11)), 90) == pytest.approx(9.1)
    assert stats.percentile([], 90) is None
    recs = [R(0, 10.0, 10.0, 8, 8, bind=10.25),
            R(1, 11.0, 11.0, 8, 8, bind=None)]
    assert sorted(stats.queue_wait_samples(recs, 10.0, 20.0)) == \
        pytest.approx([0.25, 9.0])
    assert stats.lateness([R(0, 1.0, 1.5, 8, 8)]) == [0.5]
