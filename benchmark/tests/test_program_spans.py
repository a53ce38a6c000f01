"""The program's own spans and names in a trace (``harness/program_spans.py``
and the readers on it): on hand-made spans, intervals and operations, on the
small trace ``record_program_trace.py`` recorded on the chip, and on the
older recorded trace, which holds no program span.

The recorded file holds three rounds of a hand-made tick (see the recording
script): ``sched_admit`` sleeps 0.5 ms, ``engine_prep`` 0.3 ms,
``engine_emit`` 0.4 ms, ``sched_harvest`` 0.2 ms, the benchmark's own
``harvest`` span 1 ms between the ticks (a sleep on that machine overshoots
by 0.1-0.9 ms); one program runs in each tick. The TPU profiler gives a
device event no ``op_name``, so the compiled program's text was kept beside
the trace (``recorded_program_1.hlo.txt``).
"""

import os
import types

import pytest

from benchmark.harness import manifest
from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.harness.program_spans import Span
from benchmark.harness.trace import Op, Trace

HERE = os.path.dirname(__file__)
RECORDED = os.path.join(HERE, "recorded_program_1.xplane.pb")
RECORDED_HLO = os.path.join(HERE, "recorded_program_1.hlo.txt")
OLDER = os.path.join(HERE, "recorded_1.xplane.pb")


def span(name, a, b, line=1, **stats):
    return Span(name, a, b, {k: str(v) for k, v in stats.items()}, line)


def tick(t0):
    """One serving tick of 100 ns from ``t0``, as the program nests it."""
    return [
        span("sched_tick", t0, t0 + 100, decode_seqs=2, prefill_tokens=16),
        span("sched_expire", t0 + 1, t0 + 2),
        span("sched_admit", t0 + 2, t0 + 30),
        span("prefill_batch", t0 + 5, t0 + 28),       # a one-shot prefill
        span("engine_prep", t0 + 6, t0 + 10),
        span("engine_dispatch", t0 + 10, t0 + 14),
        span("engine_wait", t0 + 14, t0 + 24),
        span("engine_emit", t0 + 24, t0 + 27),
        span("sched_preempt_guard", t0 + 30, t0 + 32),
        span("sched_step_engine", t0 + 32, t0 + 90),
        span("decode_step", t0 + 34, t0 + 88),
        span("engine_prep", t0 + 35, t0 + 40),
        span("engine_dispatch", t0 + 40, t0 + 50),
        span("engine_wait", t0 + 50, t0 + 80),
        span("engine_emit", t0 + 80, t0 + 87),
        span("sched_harvest", t0 + 90, t0 + 94),
        span("sched_retire", t0 + 94, t0 + 99),
    ]


def test_parent_is_the_innermost_containing_span_of_the_same_line():
    spans = ps.link(tick(0) + [span("other_thread", 0, 1000, line=2)])
    by = {(s.name, s.start): s for s in spans}
    name_of = lambda s: None if s.parent is None else spans[s.parent].name
    assert name_of(by["sched_tick", 0]) is None
    assert name_of(by["sched_admit", 2]) == "sched_tick"
    assert name_of(by["prefill_batch", 5]) == "sched_admit"
    assert name_of(by["engine_prep", 6]) == "prefill_batch"
    assert name_of(by["engine_prep", 35]) == "decode_step"
    assert name_of(by["decode_step", 34]) == "sched_step_engine"
    assert name_of(by["other_thread", 0]) is None      # another line
    kids = ps.children(spans, spans.index(by["sched_tick", 0]))
    assert [s.name for s in kids] == [
        "sched_expire", "sched_admit", "sched_preempt_guard",
        "sched_step_engine", "sched_harvest", "sched_retire"]


def test_self_time_is_the_duration_less_what_the_children_cover():
    spans = ps.link(tick(0))
    by = {(s.name, s.start): s for s in spans}
    assert by["sched_tick", 0].self_ns == 100 - (1 + 28 + 2 + 58 + 4 + 5)
    assert by["sched_admit", 2].self_ns == 28 - 23
    assert by["prefill_batch", 5].self_ns == 23 - (4 + 4 + 10 + 3)
    assert by["decode_step", 34].self_ns == 54 - (5 + 10 + 30 + 7)
    assert by["engine_wait", 50].self_ns == 30
    assert sum(s.self_ns for s in spans) == 100     # nothing counted twice


def test_the_four_idle_shares_partition_the_idle_time_exactly():
    spans = ps.link(tick(0) + tick(200))
    # idle: before the first tick, through its admission, its decode's
    # dispatch and the first half of the wait; then from its emit, across
    # the gap between the ticks (the benchmark's own spans) into the next
    idle = [(-20, 65), (80, 240), (290, 300)]
    split = ps.split_idle(idle, spans)
    inner = {"admit": 1 + (3 + 1 + 1 + 2) + 2,   # expire, admit's own, guard
             "dispatch": 4 + 4 + 5 + 10,          # both preps and dispatches
             "emit": 3,                           # the prefill batch's emit
             "wait": 10 + 15}
    tail = {"emit": 7 + 4 + 5, "admit": 1 + (3 + 1 + 1 + 2) + 2,
            "dispatch": 4 + 4 + 5, "emit2": 3, "wait": 10}
    assert split["admit"] == inner["admit"] + tail["admit"]
    assert split["dispatch"] == inner["dispatch"] + tail["dispatch"] \
        + inner["wait"] + tail["wait"]            # the whole round trip
    assert split["emit"] == inner["emit"] + tail["emit"] + tail["emit2"] \
        + 4 + 5                                   # second tick's harvest, retire
    # a table of its own may keep the waits apart (tools/span_report.py)
    apart = {**ps.IDLE_GROUPS, "dispatch": ("engine_prep", "engine_dispatch"),
             "wait": ("engine_wait",)}
    assert ps.split_idle(idle, spans, apart)["wait"] \
        == inner["wait"] + tail["wait"]
    assert sum(split.values()) == tr.total(idle)
    # under none: everything outside a tick (20 ns before the first, 100
    # between the two) and the slivers that sched_tick, sched_step_engine
    # and decode_step keep for themselves (1 + 2 + 1 ns at either end of
    # the first tick's engine step and at the head of the second's, and the
    # last nanosecond of the second tick)
    assert split["unattributed"] == 120 + 3 * (1 + 2 + 1) + 1


def test_an_operation_belongs_to_the_innermost_scope_of_its_op_name():
    assert ps.scope_of("jit(decode)/while/body/attn/kv_write/scatter") \
        == "kv_write"
    assert ps.scope_of("jit(decode)/while/body/attn/paged_decode/pallas_call") \
        == "attn"
    assert ps.scope_of("jit(step)/transpose(jvp(ffn))/dot_general") == "ffn"
    assert ps.scope_of("jit(step)/optimizer/norm/mul") == "norm"
    assert ps.scope_of("jit(step)/while/body/add") == ps.NO_SCOPE
    assert ps.scope_of("jit(step)/attn") == ps.NO_SCOPE   # the primitive
    assert ps.scope_of("") == ps.NO_SCOPE


def test_scope_seconds_count_every_operation_once():
    ops = [(Op("while.1", 0, 1000, "control"), "jit(f)/while"),
           (Op("fusion.1", 0, 400, "xla"), "jit(f)/while/body/attn/dot"),
           (Op("paged_decode.3", 400, 700, "mosaic"),
            "jit(f)/while/body/attn/paged_decode/pallas_call"),
           (Op("fusion.2", 700, 900, "xla"), "jit(f)/while/body/ffn/dot"),
           (Op("copy.9", 1000, 1100, "xla"), "")]
    got = ps.scope_seconds(ops, (0, 2000))
    assert got == pytest.approx({"attn": 700e-9, "ffn": 200e-9,
                                 ps.NO_SCOPE: 200e-9})
    clipped = ps.scope_seconds(ops, (500, 800))
    assert clipped == pytest.approx({"attn": 200e-9, "ffn": 100e-9})


HLO = '''HloModule jit_decode, is_scheduled=true, entry_computation_layout={...}

%fused_computation.1 (param_0: bf16[64,64]) -> bf16[64,64] {
  %param_0 = bf16[64,64]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %convolution.3 = bf16[64,64]{1,0:T(8,128)(2,1)S(1)} convolution(%param_0, %param_0), dim_labels=bf_io->bf, metadata={op_name="jit(decode)/attn/dot_general" stack_frame_id=2}
}

ENTRY %main.7 (a.1: bf16[64,64]) -> bf16[64,64] {
  %a.1 = bf16[64,64]{1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="a"}
  %fusion = bf16[64,64]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[64,64]{1,0:T(8,128)(2,1)} %a.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(decode)/attn/dot_general" stack_frame_id=2}
  %paged_decode.1 = bf16[64,64]{1,0:T(8,128)(2,1)S(1)} custom-call(bf16[64,64]{1,0:T(8,128)(2,1)S(1)} %fusion), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/attn/paged_decode/pallas_call" stack_frame_id=3}
  ROOT %fusion.2 = bf16[64,64]{1,0:T(8,128)(2,1)} fusion(%paged_decode.1, %a.1), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(decode)/ffn/dot_general" stack_frame_id=4}
}
'''


class _Compiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def event(text, a, b):
    """An operation as ``trace.load`` makes it from the profiler's event,
    which is named by the whole instruction, less its metadata."""
    name, category, label = tr.parse_op(text)
    return Op(name, a, b, category, label)


def test_an_operations_op_name_comes_from_the_program_that_was_running():
    other = HLO.replace("jit_decode", "jit_chunk_prefill") \
        .replace("/ffn/", "/logits/")
    names = ps.op_names([_Compiled(HLO), _Compiled(other), _Compiled("")])
    assert names["jit_decode", "fusion/fusion.2_bf16_64_64"] \
        == "jit(decode)/ffn/dot_general"
    assert names["jit_chunk_prefill", "fusion/fusion.2_bf16_64_64"] \
        == "jit(decode)/logits/dot_general"
    assert names["jit_decode", "mosaic/paged_decode.1_bf16_64_64"] \
        .endswith("/attn/paged_decode/pallas_call")
    shape = "bf16[64,64]{1,0:T(8,128)(2,1)S(1)}"
    ops = [event(f"%fusion = {shape} fusion({shape} %a.1), kind=kOutput, "
                 f"calls=%fused_computation.1", 10, 20),
           event(f"%paged_decode.1 = {shape} custom-call({shape} %fusion), "
                 f'custom_call_target="tpu_custom_call"', 20, 30),
           event(f"%fusion.2 = {shape} fusion(%paged_decode.1, %a.1), "
                 f"kind=kOutput, calls=%fused_computation", 30, 40),
           event(f"%fusion.2 = {shape} fusion(%paged_decode.1, %a.1), "
                 f"kind=kOutput, calls=%fused_computation", 130, 140),
           event(f"%fusion.2 = {shape} fusion(%x)", 300, 310)]
    trace = Trace({"/device:TPU:0": ops},
                  {"/device:TPU:0": [("jit_decode(123)", 5, 50),
                                     ("jit_chunk_prefill(77)", 100, 150)]},
                  [("window", 0, 400)])
    named = ps.with_op_names(trace, [_Compiled(HLO), _Compiled(other)])
    got = [ps.scope_of(op_name) for _, op_name in named["/device:TPU:0"]]
    assert got == ["attn", "attn", "ffn", "logits", ps.NO_SCOPE]


def test_span_arguments_are_numbers_where_they_can_be():
    s = span("sched_tick", 0, 1, decode_seqs=3, final=True, program="x")
    assert s.arg("decode_seqs") == 3.0 and s.arg("final") == 1.0
    assert s.arg("program") is None and s.arg("missing") is None


# -- the trace recorded on the chip ----------------------------------------- #
@pytest.fixture(scope="module")
def recorded():
    trace = tr.load(RECORDED)
    with open(RECORDED_HLO) as f:
        return trace, ps.read(RECORDED, trace, [_Compiled(f.read())])


def test_recorded_spans_nest_as_the_program_opened_them(recorded):
    trace, program = recorded
    ticks = ps.named(program.spans, "sched_tick", trace.window())
    assert len(ticks) == 3
    for t in ticks:
        kids = ps.children(program.spans, program.spans.index(t))
        assert [k.name for k in kids] == ["sched_admit", "sched_step_engine",
                                          "sched_harvest"]
        assert t.arg("decode_seqs") == 2 and t.arg("kv_tokens") >= 1000
    assert [t.arg("prefill_tokens") for t in ticks] == [0, 16, 32]
    step = ps.named(program.spans, "decode_step")[0]
    assert [k.name for k in ps.children(
        program.spans, program.spans.index(step))] == [
            "engine_prep", "engine_dispatch", "engine_wait", "engine_emit"]
    assert step.arg("batch") == 2
    for name, ms in (("sched_admit", 0.5), ("engine_prep", 0.3),
                     ("engine_emit", 0.4), ("sched_harvest", 0.2)):
        for s in ps.named(program.spans, name):
            assert ms <= s.seconds * 1e3 < ms + 1.0, (name, s.seconds)
            assert s.self_ns == s.end - s.start        # a leaf


def test_recorded_idle_time_is_partitioned_by_what_the_host_was_doing(
        recorded):
    trace, program = recorded
    window = trace.window()
    ops = next(iter(trace.devices.values()))
    idle = tr.gaps(tr.busy_intervals(ops, window), window)
    split = ps.split_idle(idle, program.spans)
    assert sum(split.values()) == pytest.approx(tr.total(idle), rel=1e-9)
    assert tr.total(idle) / 1e9 == pytest.approx(
        tr.idle_share(trace) * (window[1] - window[0]) / 1e9)
    # three ticks: each sleep is idle time under its span's group, and the
    # benchmark's own ``harvest`` (1 ms, three times) is under none
    spans = {n: sum(s.end - s.start for s in ps.named(program.spans, n))
             for n in ("sched_admit", "engine_prep", "engine_dispatch",
                       "engine_emit", "sched_harvest", "engine_wait")}
    assert split["admit"] == pytest.approx(spans["sched_admit"], rel=0.02)
    # the round trip of the dispatch: all of prep, dispatch and wait but
    # the program's 0.396 ms on the device. (The profiler's device clock is
    # about a millisecond early in this file - the program seems to run
    # during engine_prep - which is why the round trip is one group.)
    both = spans["engine_prep"] + spans["engine_dispatch"] \
        + spans["engine_wait"]
    assert split["dispatch"] == pytest.approx(both - 3 * 0.396e6, rel=0.01)
    assert split["emit"] == pytest.approx(
        spans["engine_emit"] + spans["sched_harvest"], rel=0.02)
    assert split["unattributed"] / 1e6 >= 3 * 1.0


def test_recorded_operations_fall_under_their_scope_and_kernel_name(recorded):
    trace, program = recorded
    window = trace.window()
    (plane, ops), = program.ops.items()
    assert len(ops) == len(trace.devices[plane])
    by_scope = ps.scope_seconds(ops, window)
    busy = tr.busy_seconds(trace, window)
    assert sum(by_scope.values()) == pytest.approx(busy, rel=1e-6)
    # one matmul (104.9 us) and the kernel (2.6 us) under attn, the while
    # of three matmuls (89.9 us each) under ffn, three times; the copies
    # XLA adds carry no op_name
    assert by_scope["attn"] == pytest.approx(3 * 107.5e-6, rel=0.01)
    assert by_scope["ffn"] == pytest.approx(3 * 3 * 89.9e-6, rel=0.01)
    assert by_scope[ps.NO_SCOPE] < 0.06 * busy
    kernels = [op for op, _ in ops if op.category == "mosaic"]
    assert len(kernels) == 3
    assert all(op.name.split(".")[0] == "paged_decode" for op in kernels)
    assert not [label for label, _ in tr.top_ops(trace)
                if "closed_call" in label]


class _Cell:
    def __init__(self, name, model=None, role=None):
        self.name, self.model, self.role = name, model or {}, role or {}


def _ctx(monkeypatch, path, **more):
    trace = tr.load(path)
    monkeypatch.setattr(tr, "find_xplane", lambda folder: path)
    return {"cell": _Cell("recorded", **more), "trace": trace,
            "peaks": types.SimpleNamespace(hbm_bytes_per_s=819e9)}


READERS = [
    ("idle_under_spans", {"group": "dispatch"}),
    ("idle_under_spans", {"group": "unattributed"}),
    ("span_ms_p50", {"span": "sched_tick", "minus": ["sched_admit"]}),
    ("span_arg", {"span": "sched_tick", "arg": "decode_seqs"}),
    ("span_arg", {"span": "sched_tick", "arg": "prefill_tokens",
                  "how": "share_positive"}),
    ("scope_share", {"scopes": ["attn"]}),
    ("kernel_roofline", {"kernel": "paged_decode"}),
]
MODEL = {"num_hidden_layers": 2, "num_key_value_heads": 8,
         "num_attention_heads": 32, "hidden_size": 4096}


@pytest.mark.parametrize("reader,params", READERS)
def test_every_new_reader_reports_nothing_on_a_trace_without_program_spans(
        monkeypatch, reader, params):
    ctx = _ctx(monkeypatch, OLDER, model=MODEL)
    assert ps.load(ctx) is None
    assert manifest.reader(reader).read(ctx, **params) is None
    assert manifest.reader(reader).read({"cell": ctx["cell"], "trace": None},
                                        **params) is None


def test_the_readers_on_the_recorded_trace(monkeypatch):
    ctx = _ctx(monkeypatch, RECORDED, model=MODEL,
               role={"engine": {"slots": 4}})
    with open(RECORDED_HLO) as f:
        ctx["programs"] = [_Compiled(f.read())]
    read = lambda name, **kw: manifest.reader(name).read(ctx, **kw)
    groups = ["admit", "dispatch", "emit", "unattributed"]
    shares = [read("idle_under_spans", group=g) for g in groups]
    assert sum(shares) == pytest.approx(100 * tr.idle_share(ctx["trace"]),
                                        abs=1e-9)
    assert read("span_arg", span="sched_tick", arg="decode_seqs",
                per=["engine", "slots"]) == pytest.approx(50.0)
    assert read("span_arg", span="sched_tick", arg="prefill_tokens",
                how="share_positive") == pytest.approx(200 / 3)
    assert 94.0 < read("scope_share", scopes=["attn", "ffn"]) < 96.0
    assert read("scope_share", scopes=["attn"]) == pytest.approx(
        100 * 3 * 107.5e-6 / tr.busy_seconds(ctx["trace"]), rel=0.01)
    whole = read("span_ms_p50", span="sched_tick")
    less = read("span_ms_p50", span="sched_tick", minus=["sched_admit"])
    assert 0.5 <= whole - less < 1.5
    # 1001 KV tokens a tick x 2 x 8 heads x 128 x 2 B x 2 layers, over the
    # kernel's 3 x 2.6 us: a made-up model on a kernel that reads no KV
    roofline = read("kernel_roofline", kernel="paged_decode")
    kernel_s = sum(op.seconds for op in next(iter(
        ctx["trace"].devices.values())) if op.category == "mosaic")
    assert roofline == pytest.approx(
        100 * 3003 * 8192 / 819e9 / kernel_s, rel=1e-6)
    assert read("kernel_roofline", kernel="flash_fwd") is None
