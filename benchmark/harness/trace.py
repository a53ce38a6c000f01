"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time and idle share, time by operation,
Mosaic and collective shares, the exposed part of collectives, and the idle
gaps attributed to the benchmark's own host spans.

``load`` turns the file into plain intervals (``jax.profiler.ProfileData``
reads it with nothing but JAX); everything after that is interval arithmetic
on tuples, which the tests drive with a recorded file and with hand-made
intervals alike. All times are nanoseconds on the trace's own clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from .spans import PREFIX

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
# XLA's names for the operations that move data between chips
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str          # the HLO instruction's name, e.g. ``fusion.195``
    start: float
    end: float
    category: str      # ``mosaic``, ``collective``, ``control`` or ``xla``
    label: str = ""    # ``<opcode>/<name>_<result shape>`` for the breakdown

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]                       # plane -> its ops
    modules: Dict[str, List[Tuple[str, float, float]]]  # plane -> programs
    host: List[Tuple[str, float, float]]               # benchmark spans
    asyncs: Dict[str, List[Op]] = dataclasses.field(default_factory=dict)

    def window(self) -> Interval:
        """The traced window: the benchmark's ``window`` span."""
        for name, a, b in self.host:
            if name == "window":
                return a, b
        raise ValueError("the trace holds no 'bench:window' span")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


OPCODE = re.compile(r"(?<![\w\-])([a-z][a-z0-9\-]*)\(")
SHAPE = re.compile(r"([a-z]+[0-9]+)\[([0-9,]*)\]")


def parse_op(text: str) -> Tuple[str, str, str]:
    """``(name, category, label)`` of an event of the ``XLA Ops`` line. The
    TPU profiler names such an event by the whole HLO instruction,
    ``%fusion.195 = bf16[4,2048,4096]{...} fusion(...), kind=kOutput``: the
    name is what stands before ``=``, the opcode the first lower-case word
    that opens a parenthesis after it. A Pallas kernel that Mosaic compiled
    is a ``custom-call`` whose target is ``tpu_custom_call``."""
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%").strip()
    m = OPCODE.search(rest)
    opcode = m.group(1) if m else name.split(".")[0]
    if COLLECTIVE.match(opcode):
        category = "collective"
    elif opcode == "custom-call" and "tpu_custom_call" in rest:
        category = "mosaic"
    elif opcode in ("while", "conditional", "call"):
        category = "control"
    else:
        category = "xla"
    shape = SHAPE.search(rest)
    dims = "_".join(filter(None, [shape.group(1)] + shape.group(2).split(","))) \
        if shape else ""
    prefix = "mosaic" if category == "mosaic" else opcode
    return name, category, f"{prefix}/{name}" + (f"_{dims}" if dims else "")


def _ops(line) -> List[Op]:
    out = []
    for e in line.events:
        name, category, label = parse_op(e.name)
        out.append(Op(name, e.start_ns, e.start_ns + e.duration_ns,
                      category, label))
    return out


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``. Device planes are ``/device:TPU:<n>``; their
    ``XLA Ops`` line holds one event for each executed instruction (a
    ``while`` contains its body's events), ``Async XLA Ops`` the spans of
    asynchronous copies and collectives from ``-start`` to ``-done``, and
    ``XLA Modules`` one event for each run of a jitted program. The host
    plane holds the benchmark's ``TraceAnnotation`` spans. The device's
    clock runs about a millisecond ahead of the host's in these traces, so
    the device planes are shifted until no operation starts before the
    ``window`` span does: the benchmark opens that span with the device
    idle, straight after a blocking read."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    asyncs: Dict[str, List[Op]] = {}
    modules: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = _ops(line)
                elif line.name == ASYNC_LINE:
                    asyncs[plane.name] = _ops(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append((e.name[len(PREFIX):], e.start_ns,
                                     e.start_ns + e.duration_ns))
    host.sort(key=lambda s: s[1])
    trace = Trace(devices, modules, host, asyncs)
    return shifted(trace, clock_skew(trace))


def clock_skew(trace: Trace) -> float:
    """Nanoseconds to add to device times (see :func:`load`)."""
    starts = [a for mods in trace.modules.values() for _, a, _ in mods] \
        or [o.start for ops in trace.devices.values() for o in ops]
    if not starts:
        return 0.0
    return max(0.0, trace.window()[0] - min(starts))


def shifted(trace: Trace, ns: float) -> Trace:
    if not ns:
        return trace
    move = lambda ops: [dataclasses.replace(o, start=o.start + ns,
                                            end=o.end + ns) for o in ops]
    return Trace({k: move(v) for k, v in trace.devices.items()},
                 {k: [(n, a + ns, b + ns) for n, a, b in v]
                  for k, v in trace.modules.items()},
                 trace.host, {k: move(v) for k, v in trace.asyncs.items()})


# --------------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------------- #
def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals: Sequence[Interval],
             cover: Sequence[Interval]) -> List[Interval]:
    """The parts of ``intervals`` (disjoint, sorted) that ``cover``
    (disjoint, sorted) does not touch."""
    out: List[Interval] = []
    j = 0
    for a, b in intervals:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    return subtract(a, subtract(a, b))


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    return subtract([window], busy)


# --------------------------------------------------------------------------- #
# the numbers
# --------------------------------------------------------------------------- #
def busy_intervals(ops: Iterable[Op], window: Interval,
                   keep: Optional[Callable[[Op], bool]] = None
                   ) -> List[Interval]:
    return union(clip(((o.start, o.end) for o in ops
                       if keep is None or keep(o)), window))


def busy_seconds(trace: Trace, window: Optional[Interval] = None) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    devices in the trace."""
    window = window or trace.window()
    per = [total(busy_intervals(ops, window)) / 1e9
           for ops in trace.devices.values()]
    return sum(per) / len(per) if per else 0.0


def idle_share(trace: Trace, window: Optional[Interval] = None) -> float:
    window = window or trace.window()
    return 1.0 - busy_seconds(trace, window) / ((window[1] - window[0]) / 1e9)


def self_times(ops: Sequence[Op], window: Interval) -> Dict[str, float]:
    """Seconds by operation label, each event counted for its own time only:
    an event that contains others on the same line (a ``while`` around a
    scanned layer) keeps what its children leave."""
    out: Dict[str, float] = {}
    stack: List[List] = []   # [op, seconds of children]
    def close(entry):
        op, child = entry
        a, b = max(op.start, window[0]), min(op.end, window[1])
        mine = max(0.0, (b - a) - child)
        if mine > 0:
            out[op.label or op.name] = out.get(op.label or op.name, 0.0) \
                + mine / 1e9
        return max(0.0, b - a)
    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        if op.end <= window[0] or op.start >= window[1]:
            continue
        while stack and stack[-1][0].end <= op.start:
            covered = close(stack.pop())
            if stack:
                stack[-1][1] += covered
        stack.append([op, 0.0])
    while stack:
        covered = close(stack.pop())
        if stack:
            stack[-1][1] += covered
    return out


def top_ops(trace: Trace, n: int = 10,
            window: Optional[Interval] = None) -> List[List]:
    """The ``n`` operations with the most device time (own time, averaged
    over the devices), as ``[label, seconds]``."""
    window = window or trace.window()
    acc: Dict[str, float] = {}
    for ops in trace.devices.values():
        for k, v in self_times(ops, window).items():
            acc[k] = acc.get(k, 0.0) + v / len(trace.devices)
    return [[k, v] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def category_share_of_busy(trace: Trace, category: str,
                           window: Optional[Interval] = None) -> float:
    """Device time in events of ``category`` over device busy time."""
    window = window or trace.window()
    num = den = 0.0
    for ops in trace.devices.values():
        num += total(busy_intervals(ops, window,
                                    lambda o: o.category == category))
        den += total(busy_intervals(ops, window))
    return num / den if den else 0.0


def collective_shares(trace: Trace, window: Optional[Interval] = None
                      ) -> Tuple[float, float]:
    """``(share, exposed share)`` of the window, averaged over the devices:
    the time in collective operations, and the part of it during which no
    other operation runs on that device."""
    window = window or trace.window()
    length = window[1] - window[0]
    shares, exposed = [], []
    for plane, ops in trace.devices.items():
        coll = busy_intervals(list(ops) + trace.asyncs.get(plane, []), window,
                              lambda o: o.category == "collective")
        other = busy_intervals(
            ops, window, lambda o: o.category in ("xla", "mosaic"))
        shares.append(total(coll) / length)
        exposed.append(total(subtract(coll, other)) / length)
    if not shares:
        return 0.0, 0.0
    return sum(shares) / len(shares), sum(exposed) / len(exposed)


def idle_gaps_by_span(trace: Trace, window: Optional[Interval] = None,
                      n: int = 10) -> List[List]:
    """The device's idle time inside the window, summed by the benchmark
    host span it fell in (``[span, seconds]``, longest first; idle time
    under no span is ``(no span)``). Averaged over the devices."""
    window = window or trace.window()
    spans = [(name, a, b) for name, a, b in trace.host if name != "window"]
    acc: Dict[str, float] = {}
    for ops in trace.devices.values():
        idle = gaps(busy_intervals(ops, window), window)
        left = idle
        for name in sorted({s[0] for s in spans}):
            mine = union(clip(((a, b) for n_, a, b in spans if n_ == name),
                              window))
            hit = intersect(idle, mine)
            acc[name] = acc.get(name, 0.0) + total(hit) / 1e9
            left = subtract(left, mine)
        acc["(no span)"] = acc.get("(no span)", 0.0) + total(left) / 1e9
    k = max(1, len(trace.devices))
    return [[name, v / k] for name, v in
            sorted(acc.items(), key=lambda kv: -kv[1]) if v > 0][:n]


def device_seconds_within(trace: Trace, spans: Sequence[Interval]
                          ) -> List[float]:
    """For each host interval, the device busy seconds inside it (first
    device: the serve cells run on one)."""
    ops = next(iter(trace.devices.values()), [])
    merged = busy_intervals(ops, (float("-inf"), float("inf")))
    return [total(intersect([s], merged)) / 1e9 for s in spans]


def module_durations(trace: Trace, pattern: str,
                     window: Optional[Interval] = None) -> List[float]:
    """Seconds of every execution, inside the window, of a program whose
    name matches ``pattern`` (the ``XLA Modules`` line: one event for each
    run of a jitted program)."""
    window = window or trace.window()
    rx = re.compile(pattern)
    out = []
    for mods in trace.modules.values():
        out += [(b - a) / 1e9 for name, a, b in mods
                if rx.search(name) and a >= window[0] and b <= window[1]]
    return out


def module_summary(trace: Trace, window: Optional[Interval] = None) -> Dict:
    """``{program: [runs, median seconds]}`` inside the window, for the
    run's log: the names the ``module_ms`` readers match against."""
    import statistics

    window = window or trace.window()
    by: Dict[str, List[float]] = {}
    for mods in trace.modules.values():
        for name, a, b in mods:
            if a >= window[0] and b <= window[1]:
                by.setdefault(re.sub(r"\(\d+\)$", "", name), []).append(
                    (b - a) / 1e9)
    return {k: [len(v), statistics.median(v)] for k, v in by.items()}
