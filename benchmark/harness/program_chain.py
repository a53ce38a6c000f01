"""One launched program as one chain on the timeline, and what the host
itself costs a tick: the reductions ISSUE 36's readers share, on the spans
``program_spans.py`` links (that module is the yardstick of the accepted
metrics and stays as it is; what is new lives here).

Since PR 35 a serving tick launches program n+1 before it reads program n.
The program numbers every launch (``seq``: the argument of ``decode_step``,
``prefill_chunk``, ``prefill_batch``, ``decode_quantum``, ``spec_verify``
and of the ``engine_wait`` that reads it, wherever that nests), opens
``engine_drain{cause}`` around every read of what is in flight ahead of the
tick's own, and ``sched_tick`` carries ``drains`` (docs/observability.md
"A launched program is one chain"). From those:

- **host-busy time** of a tick: the ``sched_tick`` span's duration less what
  the ``engine_wait`` spans under it cover (blocked on the device is not
  work), and its parts by ``HOST_GROUPS`` - the self times, summed a tick,
  of the spans ``program_spans.IDLE_GROUPS`` names, the wait in a group of
  its own so that it counts with none of the three;
- **the chain** of a decode-shaped program: its launch span, the run of its
  module on the device, the ``engine_wait`` that read it - joined through
  ``seq`` and order, never by guessing (``pair_runs``), and how long it
  waited in the device's queue, taken against the launches that found the
  device idle so that the two clocks' skew drops out (``queue_leads``);
- **the ticks that drained**: ``sched_tick`` spans with an ``engine_drain``
  span under them.

A program without ``seq`` (the parent commit of PR 36) gives ``None``.
"""

from __future__ import annotations

import bisect
import itertools
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from . import program_spans as ps

Interval = Tuple[float, float]

# ``IDLE_GROUPS`` with the wait apart: what the HOST does in a tick. A
# span's self time goes to its own group, else to its nearest ancestor's
# that has one (a one-shot ``prefill_batch``'s ``engine_prep`` under
# ``sched_admit`` is ``dispatch``, innermost first; the ``engine_drain``
# span's own sliver under ``sched_admit`` is ``admit``).
WAIT = "wait"
HOST_GROUPS: Dict[str, Tuple[str, ...]] = {
    "admit": ps.IDLE_GROUPS["admit"],
    "dispatch": tuple(n for n in ps.IDLE_GROUPS["dispatch"]
                      if n != "engine_wait"),
    "emit": ps.IDLE_GROUPS["emit"],
    WAIT: ("engine_wait",),
}
TICK = "sched_tick"
DRAIN = "engine_drain"


def tick_of(spans: Sequence[ps.Span]) -> List[Optional[int]]:
    """For each span the index of the ``sched_tick`` it lies under (a tick
    is its own), or ``None``. ``link`` puts a parent before its children."""
    out: List[Optional[int]] = []
    for i, s in enumerate(spans):
        out.append(i if s.name == TICK
                   else None if s.parent is None else out[s.parent])
    return out


def host_ms_by_tick(spans: Sequence[ps.Span], window: ps.Interval
                    ) -> List[Dict[str, float]]:
    """For each ``sched_tick`` wholly inside the window, in ms: ``tick`` its
    duration, ``busy`` that less the ``engine_wait`` time under it, and the
    self time under it by ``HOST_GROUPS`` (``wait`` + ``busy`` = ``tick``;
    ``admit`` + ``dispatch`` + ``emit`` <= ``busy``, the rest being what
    ``sched_tick``, ``sched_step_engine`` and the launch spans keep for
    themselves)."""
    ticks = tick_of(spans)
    rows: Dict[int, Dict[str, float]] = {
        i: dict.fromkeys(HOST_GROUPS, 0.0) for i, s in enumerate(spans)
        if s.name == TICK and s.start >= window[0] and s.end <= window[1]}
    for i, s in enumerate(spans):
        row = rows.get(ticks[i])
        group = ps.group_of(spans, i, HOST_GROUPS)
        if row is not None and group != ps.UNATTRIBUTED:
            row[group] += s.self_ns / 1e6
    for i, row in rows.items():
        row["tick"] = (spans[i].end - spans[i].start) / 1e6
        row["busy"] = row["tick"] - row[WAIT]
    return [rows[i] for i in sorted(rows)]


def drained_ticks(spans: Sequence[ps.Span]) -> Optional[List[Interval]]:
    """The intervals of the ``sched_tick`` spans that hold an
    ``engine_drain`` span; ``None`` where no tick carries a ``drains``
    argument - a program that opens no such span."""
    ticks = tick_of(spans)
    if not any(s.name == TICK and s.arg("drains") is not None for s in spans):
        return None
    held = {ticks[i] for i, s in enumerate(spans) if s.name == DRAIN}
    return [(spans[i].start, spans[i].end) for i in sorted(
        i for i in held if i is not None)]


def launches(spans: Sequence[ps.Span], names: Sequence[str]
             ) -> List[Tuple[int, ps.Span, Interval]]:
    """``(seq, launch span, its engine_dispatch child's interval)`` of every
    span of ``names`` that carries a ``seq`` and dispatched, by ``seq``."""
    dispatch: Dict[int, Interval] = {}
    for s in spans:
        if s.name == "engine_dispatch" and s.parent is not None:
            dispatch.setdefault(s.parent, (s.start, s.end))
    out = []
    for i, s in enumerate(spans):
        seq = s.arg("seq") if s.name in names else None
        if seq is not None and i in dispatch:
            out.append((int(seq), s, dispatch[i]))
    return sorted(out, key=lambda x: x[0])


def pair_runs(spans: Sequence[ps.Span], runs: Sequence[Interval],
              names: Sequence[str], slack_ns: float
              ) -> Optional[Tuple[Dict[int, Interval], Interval]]:
    """``({seq: the device run of the program launched as seq}, (low,
    high))`` for the launch spans of ``names`` and the runs (start, end) of
    their module; ``low``..``high`` is the band in which what must be ADDED
    to a device time to put it on the host's clock lies.

    Launches by ``seq`` and runs by start are both in launch order - the
    device has one queue - so the two lists pair off one to one at ONE
    offset; only a session's edges cut a chain in two (a launch before it
    opened, a run after it closed). Physics picks the offset: no run starts
    before its launch's dispatch did (``low``), and none ends after the
    ``engine_wait{seq}`` that read it returned (``high``). The offset is
    right where the band holds a skew within ``slack_ns`` (the profiler
    aligns the two clocks to a millisecond or two, by session; never by as
    much as half a run, which caps ``slack_ns``). A run or a launch the
    trace lost leaves no such offset, or two: the answer is then ``None``,
    not a guess. Neither end of the band IS the skew: a read returns a
    fraction of a millisecond after its program ends, so ``high`` (1.1-2.2
    ms on the chip, by session) is above it by a read's latency; a program
    is handed to the device at the END of a dispatch of some 4 ms, so
    ``low`` is below it by about that, and only where some launch of the
    trace found the device idle (PERF.md section 6, PR 36). What is read
    off two clocks is therefore taken as a DIFFERENCE of two such readings
    (``queue_leads``)."""
    found = launches(spans, names)
    runs = sorted(runs)
    if not found or not runs:
        return None
    index = {seq: i for i, (seq, _, _) in enumerate(found)}
    reads = [(index[int(s.arg("seq"))], s.end) for s in spans
             if s.name == "engine_wait" and s.arg("seq") is not None
             and int(s.arg("seq")) in index]
    slack_ns = min(slack_ns,
                   0.45 * statistics.median(b - a for a, b in runs))
    fits = []
    for offset in range(-len(found) + 1, len(runs)):
        at = lambda i: runs[i + offset] if 0 <= i + offset < len(runs) \
            else None
        low = max((dispatch[0] - at(i)[0] for i, (_, _, dispatch)
                   in enumerate(found) if at(i)), default=None)
        high = min((end - at(i)[1] for i, end in reads if at(i)),
                   default=None)
        if low is None or high is None or any(
                at(i) is None for i, _ in reads):   # read, so it ran
            continue
        if low <= high and low <= slack_ns and high >= -slack_ns:
            fits.append((offset, (low, high)))
    if len(fits) != 1:
        return None
    offset, band = fits[0]
    return ({seq: runs[i + offset] for i, (seq, _, _) in enumerate(found)
             if 0 <= i + offset < len(runs)}, band)


def join(spans: Sequence[ps.Span], trace, names: Sequence[str], pattern: str,
         slack_ms: float = 4.0
         ) -> Optional[Tuple[Dict[int, Interval], Interval]]:
    """``pair_runs`` for a loaded trace: the launch spans of ``names`` with
    the runs, on the ``XLA Modules`` line, of the programs whose name
    matches ``pattern`` (one queue a chip, the same programs on each: the
    first chip's runs)."""
    rx = re.compile(pattern)
    runs = [(a, b) for name, a, b in next(iter(trace.modules.values()), [])
            if rx.search(name)]
    return pair_runs(spans, runs, names, slack_ms * 1e6)


def found_idle(starts: Sequence[float], ops, gap_ns: float) -> List[bool]:
    """For each of ``starts`` (device times), whether the device had done
    nothing for ``gap_ns`` when it came: no operation of ``ops`` that began
    before it ended within ``gap_ns`` of it. Programs queued behind one
    another follow within microseconds; a launch onto an idle device comes
    a millisecond after the last upload of its dispatch. A start with no
    operation before it is the session's first work on the device, idle
    since the session opened (the tail of a program that ran across the
    opening would be in ``ops``)."""
    ops = sorted((o.start, o.end) for o in ops)
    begun = [a for a, _ in ops]
    ended = list(itertools.accumulate((b for _, b in ops), max))
    before = [bisect.bisect_left(begun, start) for start in starts]
    return [k == 0 or start - ended[k - 1] >= gap_ns
            for start, k in zip(starts, before)]


def queue_leads(spans: Sequence[ps.Span], trace, names: Sequence[str],
                pattern: str, slack_ms: float = 4.0, idle_gap_ms: float = 0.1
                ) -> Optional[List[Tuple[int, ps.Span, float, bool]]]:
    """``(seq, launch span, lead in ms, found the device idle)`` of every
    launch of ``names`` that ``join`` pairs with a run, by ``seq``. The lead
    is the run's start on the device's clock less the end of the launch's
    ``engine_dispatch`` on the host's, so it holds the skew of the two
    clocks: the same in every lead of a session, gone from the difference
    of two. A launch that found the device idle did not queue at all; its
    lead is the way to the device plus the skew, and what another launch
    reads above it is the time that one spent in the device's queue.
    ``None`` where the join is not certain or no launch carries ``seq``."""
    joined = join(spans, trace, names, pattern, slack_ms)
    if joined is None:
        return None
    paired = joined[0]
    rows = [(seq, launch, dispatch) for seq, launch, dispatch
            in launches(spans, names) if seq in paired]
    idle = found_idle([paired[seq][0] for seq, _, _ in rows],
                      next(iter(trace.devices.values()), []),
                      idle_gap_ms * 1e6)
    return [(seq, launch, (paired[seq][0] - dispatch[1]) / 1e6, was_idle)
            for (seq, launch, dispatch), was_idle in zip(rows, idle)]
