"""``judge_probes``: which served tokens the plain reference is held
against, on made-up gaps and margins."""

from benchmark.traffic_kinds.closed_loop import judge_probes


def probe(gaps, margins):
    return {"gaps": gaps, "margins": margins}


def test_a_dense_model_is_held_to_the_tolerance_at_every_position():
    assert judge_probes([probe([0.0, 0.01, 0.3], [None] * 3)]) == []
    why = judge_probes([probe([0.0, 0.01, 0.5], [None] * 3)])
    assert len(why) == 1 and "1 served tokens" in why[0]


def test_an_undecided_position_is_not_judged_and_one_decided_may_differ():
    ok = [probe([0.0, 2.1, 0.0, 0.0], [0.3, 0.01, 0.2, 0.4]),
          probe([0.6, 0.0, 0.0, 0.0], [0.06, 0.3, 0.2, 0.4])]
    assert judge_probes(ok) == []
    two = ok + [probe([0.5, 0.0], [0.2, 0.2])]
    assert "2 served tokens" in judge_probes(two)[0]


def test_too_few_decided_positions_is_no_comparison():
    why = judge_probes([probe([0.0] * 5, [0.01, 0.02, 0.03, 0.04, 0.3])])
    assert len(why) == 1 and "only 1 of 5" in why[0]
