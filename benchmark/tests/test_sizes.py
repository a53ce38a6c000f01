"""The closed loop is a function of (seed, client, n) and offers every seed
the same work."""

from collections import Counter

from benchmark.harness import manifest
from benchmark.harness.sizes import ClosedLoopPlan, size_table

CHAT = manifest.load_json(manifest.BENCH_DIR + "/traffic/chat-closed-32.json")
RAG = manifest.load_json(manifest.BENCH_DIR + "/traffic/rag-closed-16.json")


def test_request_depends_on_seed_client_and_n_only():
    a, b = ClosedLoopPlan(CHAT, 7, 32000), ClosedLoopPlan(CHAT, 7, 32000)
    for k, n in ((5, 3), (0, 0), (31, 9), (5, 3)):   # any order, repeated
        assert a.request(k, n) == b.request(k, n)
    assert a.request(5, 3)[0] != a.request(5, 4)[0]
    other = ClosedLoopPlan(CHAT, 8, 32000)
    assert other.request(5, 3)[0] != a.request(5, 3)[0]   # other tokens


def test_every_seed_offers_the_same_sizes_at_the_same_points():
    a = ClosedLoopPlan(CHAT, 1, 32000)
    b = ClosedLoopPlan(CHAT, 2 ** 31 + 12345, 32000)
    for n in range(6):
        assert [a.sizes(k, n) for k in range(32)] == \
            [b.sizes(k, n) for k in range(32)]
    # one generation of requests covers the table evenly
    assert len(Counter(a.sizes(k, 1) for k in range(32))) == 32


def test_sizes_stay_inside_the_traffic_files_limits_and_the_pool():
    for traffic, per_slot in ((CHAT, 896), (RAG, 3072)):
        table = size_table(traffic)
        p, a = traffic["prompt_tokens"], traffic["answer_tokens"]
        assert all(p["min"] <= x <= p["max"] and a["min"] <= y <= a["max"]
                   for x, y in table)
        # never preempted: the longest request fits its slot's share
        assert max(x + y for x, y in table) <= per_slot
        assert len(set(table)) == len(table)


def test_first_answers_are_staggered():
    plan = ClosedLoopPlan(CHAT, 3, 32000)
    first = sorted(plan.sizes(k, 0)[1] for k in range(plan.clients))
    assert first[0] < first[-1] / 4
