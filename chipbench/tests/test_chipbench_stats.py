"""Percentile and rate arithmetic of the end-to-end metrics."""
import pytest

from chipbench import stats


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None
    with pytest.raises(ValueError):
        stats.percentile(vals, 0)


def test_tail_is_taken_over_all_requests():
    due = [float(i) for i in range(40)]
    answered = [d + 0.1 for d in due]
    answered[7] = None                          # never answered
    lat = stats.latencies(due, answered, gave_up=100.0)
    assert len(lat) == 40
    assert max(lat) == pytest.approx(93.0)
    assert stats.percentile(lat, 95) == pytest.approx(0.1)
    answered[8] = None
    answered[9] = None
    lat = stats.latencies(due, answered, gave_up=100.0)
    assert stats.percentile(lat, 95) == pytest.approx(91.0)


def test_a_stalled_request_raises_the_tail():
    due = [0.1 * i for i in range(20)]
    steady = [d + 0.05 for d in due]
    stalled = list(steady)
    stalled[10] = due[10] + 3.0                  # one stall in the window
    p_steady = stats.percentile(stats.latencies(due, steady, 10.0), 95)
    p_stalled = stats.percentile(stats.latencies(due, stalled, 10.0), 100)
    assert p_stalled == pytest.approx(3.0)
    assert p_steady == pytest.approx(0.05)
    # a stall holds the requests queued behind it: timed from when they
    # were due, not from a late submit, they all show it
    behind = [max(a, stalled[10] + 0.01 * (i - 10)) if i >= 10 else a
              for i, a in enumerate(stalled)]
    assert stats.percentile(stats.latencies(due, behind, 10.0), 95) > 2.0


def test_solves_per_s_spans_first_submit_to_last_completion():
    rate = stats.solves_per_s(10.0, [12.0, 14.0, 15.0, 21.0], 8,
                              window_end=20.0)
    assert rate == pytest.approx(8 * 3 / 5.0)
    assert stats.solves_per_s(10.0, [25.0], 8, window_end=20.0) is None
