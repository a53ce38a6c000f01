"""Traffic kind ``closed_loop``: ``clients`` callers, each of which sends its
next request in the tick after its last reply, with no think time, through
``build_engine_v2`` + ``ServingScheduler`` and greedy sampling.

Measurement (ISSUE 23): warm-up is counted in ticks - the window opens a
fixed number of ticks after every client has had the first token of a
request - and closes at the first tick boundary at least ``--seconds``
later. A tick completes when ``scheduler.tick()`` returns, which is after
the host has read the tick's sampled tokens from the device. Tokens are
counted per tick (prompt tokens whose KV the tick wrote, plus tokens it
generated), never per completed request; the divisor is the time between
the two boundaries.
"""

from __future__ import annotations

import statistics
import time
from typing import Dict, List

import numpy as np

from ..harness import stats
from ..harness.device import jax_key
from ..harness.sizes import ClosedLoopPlan
from .common import Record, Run

# Greedy serving against the plain reference, through tokens: the served
# path computes in bf16 (paged kernel, gathered prefill), the reference in
# float32 under matmul precision "highest". Logits of a random-weight model
# have about unit variance (unit-RMS final norm times a 1/sqrt(h) head), and
# bf16 in another order moves single logits by up to 0.05-0.09 on the chip
# (PR 23, measured when this check still read logits out of the engine's
# pool). A served token is the top of the served logits, so in the
# reference's logits it lies at most twice that below the top: 0.18. A wrong
# block, offset, mask or expert moves logits by order 1, and a token from
# unrelated logits lies about 4 below the top (the largest of 32000 unit
# normals), so 0.4 separates the two.
SERVED_TOKEN_GAP_TOL = 0.4

# A sparse model chooses experts, and a choice is not continuous: where the
# reference's last chosen and first unchosen router logits (about unit
# spread) lie close together in any layer, bf16 arithmetic may rightly
# order them the other way, the served logits at that position are then
# another model's, and its token lies anywhere up to 2.2 below the
# reference's top. benchmark/tools/probe_sweep.py on the chip (PR 23: 40
# seeds, 3920 positions of the Mixtral cell): 110 positions more than 0.1
# below, 70 of them more than 0.4, every other position within 0.096; 104
# of the 110 have a margin under 0.04, the rest 0.041-0.238. So a position
# with a margin over ROUTER_MARGIN_TOL is "decided" (72 % of positions) and
# held to SERVED_TOKEN_GAP_TOL; an undecided one is not judged; a model
# that routes is allowed ONE decided position beyond the tolerance in a run
# (3 of 2814 decided positions were; two in one run of 18: none in 67 240
# windows drawn from the sweep); and a quarter of a run's positions at
# least must be decided (the fewest in any such window: 6 of 18).
ROUTER_MARGIN_TOL = 0.05
MIN_DECIDED_SHARE = 0.25


def judge_probes(probes: List[dict]) -> List[str]:
    """Why the served tokens of ``probes`` (``probe_tokens``'s results) do
    not agree with the plain reference; empty when they do."""
    gaps = np.concatenate([p["gaps"] for p in probes])
    margins = np.concatenate([[np.inf if m is None else m
                               for m in p["margins"]] for p in probes])
    decided = margins > ROUTER_MARGIN_TOL
    beyond = int((gaps[decided] > SERVED_TOKEN_GAP_TOL).sum())
    allowed = int(np.isfinite(margins).any())
    why = []
    if beyond > allowed:
        why.append(f"{beyond} served tokens lie more than "
                   f"{SERVED_TOKEN_GAP_TOL} below the top of the plain "
                   f"reference's logits where its routing is decided "
                   f"({allowed} allowed): {probes}")
    if decided.sum() < MIN_DECIDED_SHARE * len(gaps):
        why.append(f"only {int(decided.sum())} of {len(gaps)} probed "
                   f"positions have a decided routing: {probes}")
    return why


def build(r: Run):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.inference.engine_v2 import build_engine_v2

    cell = r.cell
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    module = cell.family.module()
    mesh_lib.set_mesh(None)   # the server builds its own
    dtype = jnp.dtype(cell.role["weights_dtype"])
    # one jitted init on the device, in the type the weights are served in
    params = jax.jit(lambda k: module.init(cfg, k, dtype=dtype))(
        jax_key(r.seed))
    eng = build_engine_v2(module, cfg, params, config=cell.role["engine"])
    jax.block_until_ready(eng.params)
    return eng


def probe_tokens(r: Run, eng, prompt, steps: int, uid: int) -> dict:
    """Serve ``prompt`` and ``steps`` more tokens greedily through the
    engine's public calls (a prompt longer than the SplitFuse chunk enters
    chunk by chunk), then hold every served token against the plain
    reference's logits over the prompt and the served tokens before it:
    how far below the reference's top each served token lies (``gaps``),
    and how clearly the reference chose its experts there (``margins``,
    ``None`` where the model makes no such choice)."""
    out = []
    if len(prompt) > eng.config.split_prefill_chunk:
        eng.put_split(uid, prompt)
    else:
        out.append(int(eng.put(uid, prompt)))
    while len(out) < steps + 1:
        token = eng.step().get(uid)
        if token is not None:
            out.append(int(token))
    if [int(t) for t in eng.finish(uid)] != out:
        raise RuntimeError(f"probe {uid}: finish() returns other tokens "
                           f"than put() and step() streamed")
    tokens = np.asarray(list(prompt) + out[:-1], np.int32)
    want, margin = r.cell.reference.logits_and_margin(
        r.cell.model, r.cell.family.Weights(eng.params), tokens)
    want = np.asarray(want)[len(prompt) - 1:]
    margin = np.asarray(margin)[len(prompt) - 1:]
    gaps = want.max(axis=-1) - want[np.arange(len(out)), out]
    return {"prompt": len(prompt), "served": len(out),
            "served_is_reference_top": int((gaps == 0).sum()),
            "logit_std": float(want.std()),
            "gaps": [float(g) for g in gaps],
            "margins": [float(m) if np.isfinite(m) else None
                        for m in margin]}


def warm_prefill_buckets(eng, traffic: dict, vocab: int) -> List[int]:
    """Run one prompt through every batched-prefill shape the traffic can
    reach (prompts no longer than the SplitFuse chunk pad to a multiple of
    the bucket), so that nothing compiles inside the window. The chunked
    path and the decode step are warmed by the probes."""
    bucket = eng.config.prefill_bucket
    up = lambda n: -(-n // bucket) * bucket
    chunk = up(max(eng.config.split_prefill_chunk, 1))
    lo, hi = traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]
    if eng.config.split_prefill_chunk <= 0:
        chunk = up(hi)
    shapes = list(range(up(lo), min(up(hi), chunk) + 1, bucket))
    for i, n in enumerate(shapes):
        uid = 2 * 10 ** 6 + i
        eng.put(uid, np.random.default_rng(n).integers(0, vocab, n).tolist())
        eng.step()
        eng.finish(uid)
    return shapes


class _Req:
    __slots__ = ("client", "n", "prompt_len", "answer", "handle", "ticks",
                 "submit_s", "prefilled")

    def __init__(self, client, n, prompt_len, answer, submit_s):
        self.client, self.n = client, n
        self.prompt_len, self.answer = prompt_len, answer
        self.handle = None
        self.ticks: List[int] = []     # the tick that produced each token
        self.submit_s = submit_s
        self.prefilled = 0


def run(r: Run) -> Record:
    from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                                 ServingScheduler)

    cell, t, spans = r.cell, r.cell.traffic, r.spans
    why: List[str] = []
    with spans.span("build"):
        eng = build(r)
    vocab = cell.model["vocab_size"]

    # -- correct: prefill + paged decode against the plain reference ------- #
    probe_rng = np.random.default_rng([r.seed, 0x9B0BE])
    probes = []
    with spans.span("probes"):
        for i, (n, steps) in enumerate(t["probes"]):
            probes.append(probe_tokens(
                r, eng, probe_rng.integers(0, vocab, n).tolist(), steps,
                uid=10 ** 6 + i))
    why += judge_probes(probes)
    with spans.span("warm"):
        warmed = warm_prefill_buckets(eng, t, vocab)
    r.say(phase="probes", served_token_checks=probes, warmed_prefill_shapes=warmed)

    sched = ServingScheduler(eng, SchedulerConfig(**cell.role["scheduler"]))
    plan = ClosedLoopPlan(t, r.seed, vocab)
    compiles = lambda: sum(int(s["compiles"]) for s in
                           eng.compile_monitor.summary().values())

    tick_times: List[float] = []
    generated: List[int] = []      # tokens the tick generated
    prefilled: List[int] = []      # prompt tokens whose KV the tick wrote
    decoding: List[int] = []       # sequences in the tick's decode batch
    kv_tokens: List[int] = []      # their context, in tokens, after the tick
    tick_spans: List[tuple] = []   # (start, end) of the tick span, host clock
    done: List[_Req] = []
    live: Dict[int, _Req] = {}     # client -> its request in flight
    sent = [0] * plan.clients
    rejected = 0
    now_tick = [0]
    counter = [0]

    def submit(k: int) -> None:
        nonlocal rejected
        prompt, answer = plan.request(k, sent[k])
        req = _Req(k, sent[k], len(prompt), answer, time.perf_counter())
        sent[k] += 1

        def on_token(_tok, req=req):
            req.ticks.append(now_tick[0])
            counter[0] += 1

        req.handle = sched.submit(Request(prompt=prompt,
                                          max_new_tokens=answer),
                                  on_token=on_token)
        if req.handle.state == "rejected":
            rejected += 1
            why.append(f"request ({k}, {req.n}) rejected: "
                       f"{req.handle.error}")
            raise RuntimeError(why[-1])
        live[k] = req

    def one_tick() -> None:
        with spans.span("submit"):
            for k in range(plan.clients):
                req = live.get(k)
                if req is None or req.handle.done:
                    if req is not None:
                        done.append(req)
                    submit(k)
        now_tick[0] = len(tick_times)
        counter[0] = 0
        t0 = time.perf_counter()
        with spans.span("tick"):
            sched.tick()
        t1 = time.perf_counter()
        with spans.span("harvest"):
            tick_times.append(t1)
            tick_spans.append((t0, t1))
            generated.append(counter[0])
            wrote = n_dec = ctx = 0
            seqs = eng.state.seqs
            for req in live.values():
                d = seqs.get(req.handle.uid)
                if d is None:
                    continue
                have = min(d.seen_tokens, req.prompt_len)
                wrote += have - req.prefilled
                req.prefilled = have
                if not d.prefilling:
                    n_dec += 1
                    ctx += d.seen_tokens
            prefilled.append(wrote)
            decoding.append(n_dec)
            kv_tokens.append(ctx)

    def ticks_until(cond) -> None:
        while not cond():
            one_tick()

    # fill: until every client has had a first token
    ticks_until(lambda: len(live) == plan.clients and all(
        q.ticks or q.n > 0 for q in live.values()))
    fill_ticks = len(tick_times)
    target = fill_ticks + t["warmup_ticks"]
    ticks_until(lambda: len(tick_times) >= target)
    trace_dir = None
    traced = None
    if r.trace:
        a = len(tick_times)
        with r.traced_window() as trace_dir:
            ticks_until(lambda: len(tick_times) >= a + t["trace_units"])
        traced = (a, len(tick_times))      # tick indices a .. b-1
        one_tick()                         # a boundary after the profiler
    compiles_before = compiles()
    start = len(tick_times) - 1            # the window opens here
    t_open = tick_times[start]
    ticks_until(lambda: tick_times[-1] - t_open >= r.seconds)
    _, end = stats.window_bounds(tick_times, start, r.seconds)
    compiles_in_window = compiles() - compiles_before
    if compiles_in_window:
        why.append(f"{compiles_in_window} compilations inside the window")

    requests = done + list(live.values())
    token_ticks = {i: q.ticks for i, q in enumerate(requests)}
    gaps = stats.token_gaps(token_ticks, tick_times, start, end)
    work = [g + p for g, p in zip(generated, prefilled)]
    rate = stats.window_rate(tick_times, work, start, end)
    itl_p99 = stats.percentile(gaps, 99) * 1e3
    ttft = [tick_times[q.ticks[0]] - q.submit_s for q in requests
            if q.ticks and start < q.ticks[0] <= end]
    tick_s = stats.intervals(tick_times, start, end)
    longest = max(range(len(tick_s)), key=tick_s.__getitem__)
    short = [q for q in done if len(q.handle.tokens) != q.answer]
    if short:
        why.append(f"{len(short)} requests ended without all their tokens")
    r.say(phase="serve", ticks_in_window=end - start,
          window_s=tick_times[end] - t_open, fill_ticks=fill_ticks,
          serve_tokens_per_s=rate,
          generated_in_window=sum(generated[start + 1:end + 1]),
          prefilled_in_window=sum(prefilled[start + 1:end + 1]),
          itl_p99_ms=itl_p99, itl_samples=len(gaps),
          itl_samples_beyond_p99=stats.tail_samples_beyond(len(gaps), 99),
          itl_p50_ms=stats.percentile(gaps, 50) * 1e3,
          median_tick_s=statistics.median(tick_s),
          longest_tick_s=tick_s[longest], longest_tick_index=longest,
          ttft_samples=len(ttft), requests_completed=len(done),
          compiles_in_window=compiles_in_window)
    series = {"kind": "closed_loop", "tick_completion_s": tick_times,
              "generated": generated, "prefilled": prefilled,
              "decoding": decoding, "kv_tokens": kv_tokens,
              "window": [start, end], "traced": traced,
              "ttft_s": ttft, "itl_gaps_s": gaps,
              "spans": [s for s in spans.records if s[2] >= t_open]}
    r.write_series(series)
    return Record(
        correct=not why, attempted=sum(sent), failed=rejected + len(short),
        end_to_end={"serve_tokens_per_s": rate, "itl_p99_ms": itl_p99,
                    "setup_s": t_open - r.t_process},
        context={"series": series, "window": (start, end),
                 "tick_spans": tick_spans, "traced": traced,
                 "compiles_in_window": compiles_in_window,
                 "chunk_tokens": eng.config.split_prefill_chunk},
        trace_dir=trace_dir, why_not_correct=why)
