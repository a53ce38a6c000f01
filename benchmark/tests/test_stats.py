"""The arithmetic of the rates and the tail on synthetic series: a rate is
all the work of whole units over all the time between two boundaries, so a
stall inside the window shows in it by exactly its share, and a request
that completes at the window's edge (the artefact that made PR 22's serve
rate four times noisier than its tail) does not move it."""

import pytest

from benchmark.harness import stats


def steps(n, step_s, stalls=()):
    times, t = [0.0], 0.0
    for i in range(n):
        t += step_s + (stalls[i] if i < len(stalls) else 0.0)
        times.append(t)
    return times


def test_a_stalled_step_shows_in_the_rate_by_its_share_of_the_window():
    tokens = 8192
    clean = steps(160, 0.2668)
    stalled = steps(160, 0.2668, stalls=[0.0] * 40 + [0.21])
    rates = []
    for times in (clean, stalled):
        s, e = stats.window_bounds(times, 0, 29.0)
        rates.append(stats.window_rate(times, [tokens] * len(times), s, e))
        # the window closes on a boundary, so it holds whole steps only
        assert times[e] - times[s] >= 29.0 > times[e - 1] - times[s]
    assert rates[0] == pytest.approx(tokens / 0.2668, rel=1e-12)
    # 0.21 s lost of 29 s: the stalled window's steps take 0.7 % longer
    s, e = stats.window_bounds(stalled, 0, 29.0)
    assert rates[1] == pytest.approx(
        rates[0] * (e - s) * 0.2668 / ((e - s) * 0.2668 + 0.21), rel=1e-9)
    assert 0.006 < 1 - rates[1] / rates[0] < 0.008


def test_window_holds_whole_units_and_divides_by_their_time():
    times = steps(20, 0.25)
    s, e = stats.window_bounds(times, 2, 2.5)
    assert (s, e) == (2, 12)                      # first boundary >= 2.5 s on
    assert times[e] - times[s] == pytest.approx(2.5)
    assert stats.window_rate(times, [5] * 21, s, e) == pytest.approx(50 / 2.5)
    with pytest.raises(ValueError):
        stats.window_bounds(times, 2, 30.0)


def closed_loop(edge_completes_inside):
    """100 ticks of 0.1 s; every tick prefills 256 prompt tokens and
    generates 16. One request of 2000 tokens completes in tick 90 or in
    tick 91; the window closes at tick 90."""
    times = [0.1 * i for i in range(101)]
    per_tick = [256 + 16] * 101
    completed_at = 90 if edge_completes_inside else 91
    return times, per_tick, completed_at


def test_a_request_completing_at_the_edge_does_not_move_the_tick_rate():
    rates, by_completion = [], []
    for inside in (True, False):
        times, per_tick, completed_at = closed_loop(inside)
        s, e = 10, 90
        rates.append(stats.window_rate(times, per_tick, s, e))
        # PR 22's arithmetic: a request's tokens count when it completes
        done = [2000 if i == completed_at else 0 for i in range(101)]
        steady = sum(2000 for i in range(s + 1, e) if i % 8 == 0)
        by_completion.append((steady + sum(done[s + 1:e + 1]))
                             / (times[e] - times[s]))
    assert rates[0] == rates[1] == pytest.approx(2720.0)
    assert abs(by_completion[0] - by_completion[1]) / by_completion[1] > 0.05


def test_token_gaps_belong_to_the_window_they_close_in():
    times = [float(i) for i in range(10)]
    ticks = {0: [1, 2, 4], 1: [3, 3, 9], 2: [0, 1]}
    gaps = stats.token_gaps(ticks, times, start=1, end=8)
    # (1->2) and (2->4) of request 0; (3->3) of request 1 is a gap of zero
    # (two tokens of one tick); (3->9) closes after the window; (0->1)
    # closes at the opening boundary, before it
    assert sorted(gaps) == [0.0, 1.0, 2.0]


def test_percentile_and_spread():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == pytest.approx(50.5)
    assert stats.percentile(xs, 99) == pytest.approx(99.01)
    assert stats.percentile([7.0], 99) == 7.0
    import statistics
    vals = [100.0, 101.0, 99.0, 100.5, 99.5, 100.2]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.quartile_spread(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))
    assert stats.tail_samples_beyond(3000, 99) == 30
