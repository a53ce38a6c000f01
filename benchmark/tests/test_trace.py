"""The trace reduction on the small trace recorded on the chip
(``record_trace.py``) and on hand-made intervals.

The recorded file holds three rounds of: a ``tick`` span in which one
program runs (a matmul fusion, a Pallas add that Mosaic compiled, a
``while`` of three matmul fusions; 0.396 ms on the device), then a
``harvest`` span that sleeps 2 ms - all inside one ``window`` span.
"""

import os

import pytest

from benchmark.harness import trace as tr
from benchmark.harness.trace import Op, Trace

RECORDED = os.path.join(os.path.dirname(__file__), "recorded_1.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return tr.load(RECORDED)


def test_recorded_busy_union_and_idle_share(recorded):
    lo, hi = recorded.window()
    assert (hi - lo) / 1e9 == pytest.approx(0.013060, abs=1e-5)
    runs = tr.module_durations(recorded, r"^jit_program")
    assert len(runs) == 3 and all(0.00039 < r < 0.00040 for r in runs)
    busy = tr.busy_seconds(recorded)
    # the union of the ops is the three program runs less the slivers
    # between their instructions: never more than the runs, never the sum of
    # the nested events (the ``while`` AND its body would be 1.7x)
    assert 0.97 * sum(runs) < busy <= sum(runs)
    assert tr.idle_share(recorded) == pytest.approx(
        1 - busy / ((hi - lo) / 1e9))
    assert 0.90 < tr.idle_share(recorded) < 0.92


def test_recorded_ops_are_named_categorised_and_counted_once(recorded):
    top = dict(tr.top_ops(recorded))
    # the while's own time is what its three body fusions leave
    assert top["while/while_s32"] < 1e-6
    body = top["fusion/convolution_multiply_fusion.2_bf16_2048_2048"]
    assert body == pytest.approx(9 * 89.9e-6, rel=0.01)
    assert sum(top.values()) == pytest.approx(tr.busy_seconds(recorded),
                                              rel=1e-6)
    assert "mosaic/program.1_bf16_2048_2048" in top
    share = tr.category_share_of_busy(recorded, "mosaic")
    assert share == pytest.approx(3 * 2.633e-6 / tr.busy_seconds(recorded),
                                  rel=0.01)


def test_recorded_idle_gaps_fall_under_the_host_span_that_caused_them(
        recorded):
    gaps = dict(tr.idle_gaps_by_span(recorded))
    lo, hi = recorded.window()
    idle = (hi - lo) / 1e9 - tr.busy_seconds(recorded)
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    # three sleeps of 2 ms (2.9-3.0 ms with the span's own cost)
    assert gaps["harvest"] == pytest.approx(0.0090, abs=0.0005)
    # the rest of each tick: dispatch before, the blocking read after
    assert gaps["tick"] == pytest.approx(0.0027, abs=0.0005)
    assert gaps.get("(no span)", 0.0) < 0.0003


def test_recorded_device_clock_is_aligned_to_the_window(recorded):
    first = min(a for mods in recorded.modules.values() for _, a, _ in mods)
    assert first >= recorded.window()[0]
    ticks = [(a, b) for n, a, b in recorded.host if n == "tick"]
    inside = tr.device_seconds_within(recorded, ticks)
    assert all(0.00037 < s < 0.00040 for s in inside)


def op(name, a, b, category="xla"):
    return Op(name, a, b, category, name)


def test_exposed_collective_time_is_what_no_other_operation_covers():
    ops = [op("fusion.1", 0, 40), op("fusion.2", 60, 100),
           op("while", 0, 100, "control"),
           op("all-reduce.1", 30, 70, "collective")]
    asyncs = [op("all-gather-start.1", 90, 130, "collective")]
    t = Trace({"/device:TPU:0": ops}, {}, [("window", 0, 200)],
              {"/device:TPU:0": asyncs})
    share, exposed = tr.collective_shares(t)
    assert share == pytest.approx((40 + 40) / 200)
    # 40..60 of the all-reduce and 100..130 of the gather stand alone; the
    # enclosing while does not hide them
    assert exposed == pytest.approx((20 + 30) / 200)


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.intersect([(0, 10)], [(2, 3), (5, 12)]) == [(2, 3), (5, 10)]
    assert tr.gaps([(2, 3)], (0, 5)) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 4), (6, 9)], (3, 7)) == [(3, 4), (6, 7)]


def test_parse_op_reads_the_hlo_text_the_tpu_profiler_writes():
    name, cat, label = tr.parse_op(
        '%program.1 = bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)} custom-call('
        'bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)} %fusion), '
        'custom_call_target="tpu_custom_call"')
    assert (name, cat, label) == ("program.1", "mosaic",
                                  "mosaic/program.1_bf16_2048_2048")
    assert tr.parse_op("%all-gather-start.3 = (bf16[4,8]{1,0}, bf16[16,8]"
                       "{1,0}) all-gather-start(bf16[4,8]{1,0} %p), "
                       "dimensions={0}")[1] == "collective"
    assert tr.parse_op("%while = (s32[]{:T(128)}, bf16[8]{0}) while((s32[]"
                       "{:T(128)}) %tuple.13), condition=%c, body=%b"
                       )[1] == "control"
    assert tr.parse_op("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %x), "
                       "kind=kLoop")[1:] == ("xla", "fusion/fusion.9_f32_8")
