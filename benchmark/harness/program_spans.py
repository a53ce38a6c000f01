"""The program's own spans and names in a profiler trace.

Since PR 24 every ``Tracer.span`` of the program is on the profiler's
timeline as ``dstpu:<name>`` with its arguments as the event's stats
(``deepspeed_tpu/telemetry/trace.py``), its Pallas kernels carry a name,
and the blocks of the model step are named scopes in the ``op_name`` of the
compiled operations. This module reduces the same ``.xplane.pb`` that
``harness/trace.py`` reduces to what the ``program_span`` metrics read:

- the spans, each with its parent (by containment on its thread's line) and
  its self time (its duration less what its children cover);
- the device's idle time split by the group of spans the host was in;
- device time by named scope.

A run's trace is still on disk when the readers run (``run.py`` removes it
afterwards), so ``load(ctx)`` opens it again through
``harness.trace.find_xplane``. Host spans need no clock shift: ``trace.load``
moved the device planes onto the host's clock, and the device operations
are taken from that loaded trace (``ctx["trace"]``). The TPU profiler names
a device event by its HLO instruction and gives it no ``op_name``
(confirmed on the chip, PR 24), so the scope of an operation comes from the
compiled programs' own text (``ctx["programs"][i].as_text()``): the program
run that contains the event in time names the module, the event's
instruction the line. A trace without ``dstpu:`` spans - the parent commit of PR 24
- loads as ``None``, and every reader built on this module then reports
nothing. Interval arithmetic is ``harness/trace.py``'s, as it is.
"""

from __future__ import annotations

import bisect
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import trace as tr
from .manifest import ROOT

PREFIX = "dstpu:"
Interval = Tuple[float, float]

# The serving tick's spans by what the host is doing (docs/observability.md
# "Spans on the profiler timeline"). A moment belongs to the innermost span
# that covers it; if that span is in no group, to its nearest ancestor that
# is; else to no group. ``dispatch`` is the whole round trip of a dispatch,
# ``engine_wait`` included: how the idle time around a dispatch divides
# between the way to the device and the way back depends on the profiler's
# alignment of the device clock, which moves by about a millisecond from one
# session to the next (two traced runs of one cell, PR 24: 61 % / 32 % of the
# idle time and 43 % / 49 %; the sums 92.9 % and 92.3 %).
IDLE_GROUPS: Dict[str, Tuple[str, ...]] = {
    "admit": ("sched_expire", "sched_admit", "sched_preempt_guard"),
    "dispatch": ("engine_prep", "engine_dispatch", "engine_wait"),
    "emit": ("engine_emit", "sched_harvest", "sched_retire"),
}
UNATTRIBUTED = "unattributed"

# The named scopes of the model step (``jax.named_scope`` in
# models/{llama,mixtral,_paged}.py, moe/layer.py, inference/sampling.py and
# runtime/engine.py). An operation belongs to the innermost of them in its
# ``op_name``; under autodiff a scope arrives wrapped, ``transpose(jvp(attn))``.
SCOPES = ("embed", "norm", "attn", "kv_write", "ffn", "moe_router",
          "moe_experts", "logits", "sample", "loss", "optimizer")
NO_SCOPE = "(no scope)"
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%\S+ = .*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_RUN_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Span:
    name: str                      # without the ``dstpu:`` prefix
    start: float                   # ns, host clock
    end: float
    stats: Dict[str, str]
    line: int                      # its thread's line in the trace
    parent: Optional[int] = None   # index into the list it is in
    self_ns: float = 0.0           # duration less what its children cover

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9

    def arg(self, key: str) -> Optional[float]:
        """A numeric argument of the span (``True``/``False`` as 1/0)."""
        value = self.stats.get(key)
        if value is None:
            return None
        if str(value) in ("True", "False"):
            return float(str(value) == "True")
        try:
            return float(value)
        except (TypeError, ValueError):
            return None


def link(spans: List[Span]) -> List[Span]:
    """Sort the spans, give each its parent - the innermost span of the same
    line that contains it - and its self time."""
    spans.sort(key=lambda s: (s.line, s.start, -s.end))
    stack: List[int] = []
    for i, s in enumerate(spans):
        while stack and (spans[stack[-1]].line != s.line
                         or spans[stack[-1]].end < s.end
                         or spans[stack[-1]].end <= s.start):
            stack.pop()
        s.parent = stack[-1] if stack else None
        s.self_ns = s.end - s.start
        if s.parent is not None:
            spans[s.parent].self_ns -= s.end - s.start
        stack.append(i)
    return spans


def children(spans: Sequence[Span], index: int) -> List[Span]:
    return [s for s in spans if s.parent == index]


def named(spans: Sequence[Span], name: str,
          window: Optional[Interval] = None) -> List[Span]:
    """The spans called ``name`` that lie wholly inside the window."""
    return [s for s in spans if s.name == name and (
        window is None or (s.start >= window[0] and s.end <= window[1]))]


def group_of(spans: Sequence[Span], index: Optional[int],
             groups: Dict[str, Iterable[str]] = IDLE_GROUPS) -> str:
    """The group of the span or of its nearest ancestor that has one."""
    while index is not None:
        for group, names in groups.items():
            if spans[index].name in names:
                return group
        index = spans[index].parent
    return UNATTRIBUTED


def intervals_by_group(spans: Sequence[Span],
                       groups: Dict[str, Iterable[str]] = IDLE_GROUPS
                       ) -> Dict[str, List[Interval]]:
    """For each group the time in which the innermost span open on its line
    belongs to it (see ``IDLE_GROUPS``): the spans' own intervals less their
    children's, merged. Disjoint between groups on one line."""
    kids: Dict[int, List[Interval]] = {}
    for s in spans:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append((s.start, s.end))
    out: Dict[str, List[Interval]] = {g: [] for g in groups}
    for i, s in enumerate(spans):
        group = group_of(spans, i, groups)
        if group != UNATTRIBUTED:
            out[group] += tr.subtract([(s.start, s.end)],
                                      tr.union(kids.get(i, [])))
    return {g: tr.union(v) for g, v in out.items()}


def split_idle(idle: Sequence[Interval], spans: Sequence[Span],
               groups: Dict[str, Iterable[str]] = IDLE_GROUPS
               ) -> Dict[str, float]:
    """Nanoseconds of ``idle`` (disjoint, sorted) under each group, and the
    rest under ``unattributed``. The parts add up to the whole: a moment two
    threads' spans both cover goes to the first group in the table's order."""
    out: Dict[str, float] = {}
    left = list(idle)
    for group, mine in intervals_by_group(spans, groups).items():
        out[group] = tr.total(tr.intersect(left, mine))
        left = tr.subtract(left, mine)
    out[UNATTRIBUTED] = tr.total(left)
    return out


def scope_of(op_name: str, scopes: Sequence[str] = SCOPES) -> str:
    """The innermost named scope in an HLO ``op_name`` such as
    ``jit(decode)/while/body/attn/kv_write/scatter``; the last component is
    the primitive, not a scope."""
    for part in reversed(op_name.split("/")[:-1]):
        for word in reversed(_WORD.findall(part)):
            if word in scopes:
                return word
    return NO_SCOPE


def scope_seconds(ops: Sequence[Tuple[tr.Op, str]], window: Interval,
                  scopes: Sequence[str] = SCOPES) -> Dict[str, float]:
    """Device seconds by scope inside the window, each operation counted for
    its own time only (a ``while`` keeps what its body's operations leave):
    ``trace.self_times`` over the operations relabelled by scope."""
    return tr.self_times(
        [dataclasses.replace(op, label=scope_of(op_name, scopes))
         for op, op_name in ops], window)


@dataclasses.dataclass
class Program:
    spans: List[Span]
    ops: Dict[str, List[Tuple[tr.Op, str]]]   # plane -> (operation, op_name)


def op_names(programs: Iterable) -> Dict[Tuple[str, str], str]:
    """``(module, operation label) -> op_name`` from the text of compiled
    programs (``jax.stages.Compiled.as_text()``: one instruction a line with
    ``metadata={op_name="..."}``). The label is ``trace.parse_op``'s - the
    opcode, the instruction's name and its result's dimensions - so that two
    programs of one module name (a final and a non-final chunk) whose
    numbering differs do not lend each other a scope."""
    out: Dict[Tuple[str, str], str] = {}
    for program in programs:
        text = program.as_text()
        module = _MODULE.match(text)
        if module is None:
            continue
        for line in text.splitlines():
            instruction = _INSTRUCTION.match(line)
            op_name = _OP_NAME.search(line)
            if instruction and op_name:
                out.setdefault(
                    (module.group(1), tr.parse_op(instruction.group(1))[2]),
                    op_name.group(1))
    return out


def with_op_names(trace: tr.Trace, programs: Iterable
                  ) -> Dict[str, List[Tuple[tr.Op, str]]]:
    """Every device operation of the loaded trace beside the ``op_name`` its
    instruction has in the program that was running (``""`` if none)."""
    names = op_names(programs)
    out: Dict[str, List[Tuple[tr.Op, str]]] = {}
    for plane, ops in trace.devices.items():
        runs = sorted((a, b, _RUN_ID.sub("", name))
                      for name, a, b in trace.modules.get(plane, []))
        starts = [a for a, _, _ in runs]
        out[plane] = []
        for op in ops:
            i = bisect.bisect_right(starts, op.start) - 1
            module = runs[i][2] if i >= 0 and op.end <= runs[i][1] else ""
            out[plane].append((op, names.get((module, op.label), "")))
    return out


def read(path: str, trace: Optional[tr.Trace] = None,
         programs: Iterable = ()) -> Optional[Program]:
    """The program's spans in the file, and the loaded trace's device
    operations beside their ``op_name``. ``None`` if the file holds no
    ``dstpu:`` span."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    line_id = 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                line_id += 1
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append(Span(
                            e.name[len(PREFIX):], e.start_ns,
                            e.start_ns + e.duration_ns,
                            {k: str(v) for k, v in e.stats}, line_id))
    if not spans:
        return None
    return Program(link(spans),
                   with_op_names(trace, programs) if trace else {})


def load(ctx) -> Optional[Program]:
    """The traced run's program spans, or ``None``: no trace, no file, or a
    program without spans on the timeline. Read once a run and kept in the
    run's context, which every reader is handed."""
    if ctx.get("trace") is None:
        return None
    if "program_spans" not in ctx:
        try:
            path = tr.find_xplane(os.path.join(
                ROOT, "benchmark_out", ctx["cell"].name, "trace"))
        except FileNotFoundError:
            return None
        ctx["program_spans"] = read(path, ctx["trace"],
                                    ctx.get("programs") or ())
    return ctx["program_spans"]
