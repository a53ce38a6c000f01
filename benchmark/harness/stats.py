"""The yardstick's arithmetic: percentiles, spreads, and the rates over
whole steps and whole ticks.

Everything here is a pure function of recorded series, so the tests can show
on synthetic series what a stalled step or a request completing at the
window's edge does to each metric (the two artefacts that made the first
attempt at this benchmark too noisy for its own bound).
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """``q``-th percentile (0..100), linear interpolation between order
    statistics - numpy's default rule, written out so the yardstick depends
    on nothing that can change."""
    if not values:
        raise ValueError("percentile of an empty series")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)`` -
    the spread the driver's check uses."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def window_bounds(times: Sequence[float], start_index: int,
                  seconds: float) -> Tuple[int, int]:
    """The measured window over a series of completion times of whole units
    (steps or ticks). It opens at the completion of unit ``start_index`` (the
    last warm-up unit) and closes at the first completion at least
    ``seconds`` later. Returns ``(start_index, end_index)``; the units in the
    window are ``start_index + 1 .. end_index`` and its length is
    ``times[end_index] - times[start_index]``: whole units only, and the
    divisor is device-complete time, never ``seconds``."""
    t0 = times[start_index]
    for i in range(start_index + 1, len(times)):
        if times[i] - t0 >= seconds:
            return start_index, i
    raise ValueError(
        f"the series ends {times[-1] - t0:.3f} s after the window opens, "
        f"before the {seconds} s it has to cover")


def intervals(times: Sequence[float], start: int, end: int) -> List[float]:
    return [times[i] - times[i - 1] for i in range(start + 1, end + 1)]


def window_rate(times: Sequence[float], counts: Sequence[float],
                start: int, end: int) -> float:
    """Sum of the per-unit counts of the window's units over the
    device-complete time between its two boundaries: all the work over all
    the time, so a stall inside the window shows in the rate."""
    return sum(counts[start + 1:end + 1]) / (times[end] - times[start])


def token_gaps(token_ticks: Dict[int, List[int]], tick_times: Sequence[float],
               start: int, end: int) -> List[float]:
    """Gaps between consecutive output tokens of one request, for every
    request, taken from the completion time of the tick that produced each
    token. A gap belongs to the window when it CLOSES there: its later token
    came from a tick in ``start + 1 .. end``."""
    gaps = []
    for ticks in token_ticks.values():
        for a, b in zip(ticks, ticks[1:]):
            if start < b <= end:
                gaps.append(tick_times[b] - tick_times[a])
    return gaps


def tail_samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return int(n * (100.0 - q) / 100.0)
