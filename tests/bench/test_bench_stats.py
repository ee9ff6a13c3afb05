"""Metric arithmetic: exact percentiles over every sample, censored waits,
and rates over all work and all time."""

import dataclasses

import numpy as np
import pytest

from benchlib import readings, stats


@dataclasses.dataclass
class R:
    due: float
    token_t: list
    admitted_t: float = None


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
def test_percentile_matches_numpy(q):
    xs = list(np.random.default_rng(0).lognormal(size=137))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 50) is None


def test_ttft_counts_censored_waits():
    recs = [R(due=1.0, token_t=[1.5, 1.6]),      # served: 0.5
            R(due=2.0, token_t=[]),              # still waiting at 10: 8
            R(due=3.0, token_t=[12.0]),          # first token after close: 7
            R(due=0.5, token_t=[0.9]),           # due before the window
            R(due=10.0, token_t=[10.1])]         # due at the close: out
    assert sorted(stats.ttft_samples(recs, 1.0, 10.0)) == [0.5, 7.0, 8.0]


def test_itl_counts_gaps_ending_in_window():
    recs = [R(due=0, token_t=[0.5, 1.5, 2.0, 11.0]), R(due=0, token_t=[3.0])]
    assert stats.itl_samples(recs, 1.0, 10.0) == [1.0, 0.5]


def test_queue_wait_censored():
    recs = [R(due=1.0, token_t=[], admitted_t=1.25),
            R(due=2.0, token_t=[], admitted_t=None)]
    assert stats.queue_wait_samples(recs, 0.0, 5.0) == [0.25, 3.0]


def test_rate_is_all_work_over_all_time():
    @dataclasses.dataclass
    class W:
        records: list
        t_start: float
        t_end: float

    @dataclasses.dataclass
    class C:
        window: W

    recs = [R(due=0, token_t=[0.5, 1.0, 2.0, 3.0]),
            R(due=0, token_t=[1.5, 4.5, 6.0])]
    # 5 tokens in [1, 5] over 4 s, whatever the gaps between them.
    assert readings.out_tok_s(C(W(recs, 1.0, 5.0))) == pytest.approx(1.25)
