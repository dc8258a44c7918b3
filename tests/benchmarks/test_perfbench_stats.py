"""Percentile and rate arithmetic on hand-made samples."""

import math

import pytest

from benchmarks.harness import stats as st
from benchmarks.harness.context import Context
from benchmarks.harness.loadgen import Row
from benchmarks.harness.traffic import Request


@pytest.mark.parametrize("q,want", [(0.5, 5), (0.9, 9), (1.0, 10),
                                    (0.1, 1), (0.95, 10)])
def test_nearest_rank_percentile(q, want):
    assert st.percentile(range(1, 11), q) == want


def test_percentile_of_nothing_and_bad_q():
    assert st.percentile([], 0.5) is None
    with pytest.raises(ValueError):
        st.percentile([1], 0)


def test_a_miss_ranks_above_every_finished_request():
    vals = [10.0] * 8 + [st.MISS, st.MISS]
    assert st.percentile(vals, 0.8) == 10.0
    assert st.percentile(vals, 0.9) == st.MISS
    assert st.tail_or_limit(vals, 0.9, 75000.0) == 75000.0
    assert st.tail_or_limit(vals, 0.5, 75000.0) == 10.0


@pytest.mark.parametrize("n,q,beyond", [(100, 0.9, 10), (120, 0.9, 12),
                                        (10, 0.9, 1), (0, 0.9, 0)])
def test_samples_beyond(n, q, beyond):
    assert st.samples_beyond(n, q) == beyond


def test_tpot():
    assert st.tpot_ms(1.0, 1.9, 10) == pytest.approx(100.0)
    assert st.tpot_ms(1.0, 1.9, 1) is None
    assert st.tpot_ms(None, 1.9, 10) is None
    assert st.tpot_ms(1.0, None, 10) is None


def test_rate_and_iqr():
    assert st.rate(900, 45.0) == 20.0
    with pytest.raises(ValueError):
        st.rate(1, 0)
    assert st.iqr_share([100, 101, 102, 103, 104, 105]) == pytest.approx(
        (104.25 - 100.75) / 102.5)


# ----------------------------------------- the end-to-end metrics of a run


class Stream:
    def __init__(self, first, finish, tokens, reason="length"):
        self.first_token_time, self.finish_time = first, finish
        self.token_ids = [7] * tokens
        self.finish_reason = reason


def row(due, first, finish, tokens, want=None, reason="length", error=None):
    req = Request(0, [3] * 10, want if want is not None else tokens, 1)
    stream = None if error else Stream(first, finish, tokens, reason)
    return Row(req, due, due + 0.001, stream=stream, error=error)


def ctx_of(rows):
    return Context(cell=None, rows=rows, t0=0.0, t_end=10.0,
                   drain_limit_s=5.0)


def test_ttft_counts_from_due_and_a_failure_is_a_miss():
    rows = [row(1.0, 1.2, 2.0, 9) for _ in range(8)]
    rows.append(row(1.0, None, None, 0, error="SchedulerFullError: shed"))
    rows.append(row(1.0, 1.1, None, 3, want=9, reason=None))   # unfinished
    c = ctx_of(rows)
    ttft = c.ttft_ms()
    assert ttft[:8] == pytest.approx([200.0] * 8)
    assert ttft[8] == st.MISS and ttft[9] == st.MISS
    assert c.end_to_end("ttft_p50_ms") == pytest.approx(200.0)
    # the 90th percentile falls on a miss: the limit stands in its place
    assert c.end_to_end("ttft_p90_ms") == pytest.approx(15000.0)
    assert len(c.failed_rows()) == 2


def test_ttft_tail_falls_on_a_finished_request_while_misses_are_few():
    rows = [row(1.0, 1.0 + 0.1 * (i + 1), 3.0, 9) for i in range(19)]
    rows.append(row(1.0, None, None, 0, error="SchedulerFullError: shed"))
    c = ctx_of(rows)
    # one miss in twenty ranks last: the 90th percentile is the 18th
    assert c.end_to_end("ttft_p90_ms") == pytest.approx(1800.0)
    assert c.end_to_end("ttft_p99_ms") == pytest.approx(15000.0)


def test_a_request_that_stopped_short_is_not_ok():
    c = ctx_of([row(0.0, 0.1, 1.0, 5, want=9)])
    assert not c.ok(c.rows[0]) and c.tpot_ms() == []


def test_tpot_percentiles_over_finished_requests():
    rows = [row(0.0, 1.0, 1.0 + 0.010 * (i + 1) * 9, 10) for i in range(10)]
    c = ctx_of(rows)
    assert c.end_to_end("tpot_p50_ms") == pytest.approx(50.0)
    assert c.end_to_end("tpot_p90_ms") == pytest.approx(90.0)


def test_out_tok_per_s_counts_window_tokens_of_correct_requests():
    a, b = row(0.0, 0.5, 4.0, 100), row(0.0, 0.5, 12.0, 100)
    b.tokens_in_window = 60                 # the rest came in the drain
    bad = row(0.0, 0.5, 3.0, 40, want=100)  # stopped short: not counted
    c = ctx_of([a, b, bad])
    assert c.end_to_end("out_tok_per_s") == pytest.approx(16.0)


def test_unknown_metric_raises():
    with pytest.raises(KeyError):
        ctx_of([]).end_to_end("goodput")


def test_occupancy_from_the_generators_stamps():
    c = ctx_of([row(0.0, 1.0, 3.0, 20), row(0.0, 2.0, 4.0, 20)])
    c.trace_t0, c.trace_t1 = 2.0, 3.0
    rows_mean, kv = c.mean_occupancy(samples=1000)
    assert rows_mean == pytest.approx(2.0)
    # contexts: 10 + 20*(t-1)/2 and 10 + 20*(t-2)/2, mean over [2,3]
    assert kv == pytest.approx(10 + 15 + 10 + 5, rel=1e-3)
    # the rows one by one: a cost that is not linear in a row's context
    # (a window) is taken per row, then averaged
    occ = c.occupancy(samples=1000)
    assert len(occ) == 1000 and all(len(a) == 2 for a in occ)
    assert occ[0][0] == pytest.approx(20.0, abs=0.02)
    assert occ[0][1] == pytest.approx(10.0, abs=0.02)
    capped = c.mean_occupancy(lambda a: sum(min(x, 20.0) for x in a), 1000)
    assert capped[1] == pytest.approx(20 + 15, rel=1e-3)
    c.trace_t0, c.trace_t1 = 8.0, 9.0
    assert c.occupancy() is None and c.mean_occupancy() is None
    assert math.isinf(st.MISS)
