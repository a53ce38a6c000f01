#!/usr/bin/env python
"""Steady-state + open-loop serving benchmarks (VERDICT r3 item 5).

Drives the continuous-batching v2 engine with a mixed prefill/decode workload:
a closed-loop client keeps `batch` sequences live — whenever one finishes, a
new prompt is admitted — so every measured step interleaves decode with
periodic prefills exactly the way FastGen's steady-state benchmark does
(reference blogs/deepspeed-fastgen: throughput at fixed client count).

Every workload draws its prompts from ``inference.serving.workload``
(seeded TrafficGenerator), and one shared closed-loop driver
(``run_closed_loop``) measures them all. Reports generated tok/s at 2-3
client counts, plus a shared-system-prompt workload (N clients sharing a
long common prefix) that measures the paged engine's prefix cache ON vs
OFF: tok/s, hit-rate, and prefill_tokens_saved (docs/serving.md), a
decode-heavy workload (short repetitive prompts, long generations) that
measures speculative decoding OFF vs ON: tok/s, accept rate, ITL p50/p99
and model forward passes per generated token, and an OPEN-LOOP Poisson
workload replayed against the continuous-batching scheduler vs the
hand-rolled FCFS admit loop — goodput-under-SLO, queue-wait percentiles,
and preemption counts — plus a fleet
CHAOS probe (``detail.chaos``): the same trace on a two-replica fleet,
fault-free vs with a mid-trace replica crash, reporting the goodput delta
that failover + circuit-breaker re-admission leave behind, and a
quantized-KV workload (``detail.kvquant``, gate ``DSTPU_BENCH_KVQUANT=0``):
int8 KV blocks at EQUAL pool bytes vs bf16 — resident sequences, decode
tok/s, ITL p50/p99, per-token logit MAE, greedy stream identity
(docs/serving.md "Quantized KV cache").
ONE JSON line.
"""

import json
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _probe_common import finalize  # noqa: E402

# stdout must carry exactly ONE JSON line; the package logger defaults to
# stdout, so route it to stderr before any deepspeed_tpu import
logging.basicConfig(stream=sys.stderr)

# vs_baseline is null: FastGen's published rows are 7-70B models on A100
# clusters — no comparable per-chip 235M row exists to divide by
RESULT = {"metric": "serving_steady_tok_per_sec", "value": 0.0,
          "unit": "tok/s", "vs_baseline": None, "detail": {}}


def run_closed_loop(eng, sp, traffic, batch, gen_len, measure_s, quantum=1):
    """THE shared closed-loop driver (admission boilerplate lives here once;
    the steady-state, shared-prefix, and decode-heavy workloads differ only
    in the ``traffic`` generator feeding it): keep ``batch`` sequences live
    for ``measure_s`` seconds, admitting a fresh prompt from ``traffic``
    whenever one finishes, and count generated tokens (decode steps + the
    first token each prefill produces). ``quantum > 1`` uses the fused
    k-step decode (one host sync per k tokens) with admission at quantum
    boundaries. Returns a row dict: tok/s, prefills-in-window, per-token
    call latency (call time / quantum — the FastGen-comparable number), and
    emission-weighted ITL p50/p99 (a speculative step emits several tokens,
    so each token's ITL is the step time over the tokens it produced)."""
    import numpy as np

    uid = 0

    def admit():
        nonlocal uid
        eng.put(uid, traffic.prompt_tokens(), sp, seed=uid)
        uid += 1

    def useful_live():
        """Served tokens currently held by live sequences, capped at
        gen_len — overshoot past gen_len (quantum tail) is NOT throughput."""
        return sum(min(len(d.generated), gen_len)
                   for d in eng.state.seqs.values())

    def step():
        if quantum > 1:
            eng.step_many(quantum, sp)
        else:
            eng.step(sp)

    for _ in range(batch):
        admit()
    step()                       # warm the decode program
    base = useful_live()         # pre-window tokens never count
    t0 = time.perf_counter()
    produced_retired = 0
    prefills = 0
    call_ms = []                 # per-call wall time → token latency
    itl_ms = []                  # per-emitted-token latency
    while time.perf_counter() - t0 < measure_s:
        before = useful_live()
        tc = time.perf_counter()
        step()
        dt_ms = (time.perf_counter() - tc) * 1e3
        call_ms.append(dt_ms)
        emitted = max(1, useful_live() - before)
        itl_ms.extend([dt_ms / emitted] * emitted)
        for d in list(eng.state.seqs.values()):
            if len(d.generated) >= gen_len:
                produced_retired += gen_len
                eng.finish(d.uid)
                admit()          # prefill happens inside the measured loop
                prefills += 1
    dt = time.perf_counter() - t0
    produced = produced_retired + useful_live() - base
    for d in list(eng.state.seqs.values()):
        eng.finish(d.uid)
    # FastGen-comparable per-token latency: a quantum call emits `quantum`
    # tokens per sequence, so token latency = call time / quantum
    tok_ms = np.asarray(call_ms) / max(1, quantum)
    itl = np.asarray(itl_ms)
    return {"tok_per_sec": round(produced / dt, 1),
            "tokens_in_window": int(produced),
            "prefills_in_window": prefills,
            "model_steps": len(call_ms),
            "token_latency": {
                "p50_ms": round(float(np.percentile(tok_ms, 50)), 2),
                "p95_ms": round(float(np.percentile(tok_ms, 95)), 2)},
            "itl_p50_ms": round(float(np.percentile(itl, 50)), 2),
            "itl_p99_ms": round(float(np.percentile(itl, 99)), 2)}


def _traffic(**kw):
    from deepspeed_tpu.inference.serving import (TrafficGenerator,
                                                 WorkloadConfig)

    return TrafficGenerator(WorkloadConfig(**kw))


def _warm_engine(eng, sp, vocab, lengths, max_batch, quantum=1):
    """Compile the prefill/decode programs a replay will hit OUTSIDE the
    measured window (power-of-two admission-burst shapes at each prompt
    length, the prefix-cache ctx variants via a second pass, and the decode
    program). Compiles are a one-time cost the persistent XLA cache absorbs
    in production; inside the window they would measure compilation, not
    scheduling or fault-handling policy."""
    import numpy as np

    wrng = np.random.default_rng(999)
    uid = 10 ** 6
    for hi in lengths:
        n = 1
        while n <= max_batch:
            prompt = wrng.integers(0, vocab, (hi,), dtype=np.int32).tolist()
            for _ in range(2):   # second pass hits the cache → ctx variant
                pairs = [(uid + j, prompt) for j in range(n)]
                eng.put_many(pairs, sp, seed=0)
                if quantum > 1:
                    eng.step_many(quantum, sp)
                else:
                    eng.step(sp)
                for u, _ in pairs:
                    eng.finish(u)
                uid += n
            n *= 2


def run_shared_prefix(build, sp, vocab, batch, shared_len, tail_len,
                      gen_len, measure_s, quantum=1):
    """Shared-system-prompt workload (docs/serving.md): ``batch`` closed-loop
    clients whose prompts all start with the SAME ``shared_len``-token prefix
    (a long system prompt / few-shot template) followed by a unique tail.
    Runs the loop with the prefix cache OFF then ON and reports tok/s,
    prefix hit-rate, ``prefill_tokens_saved``, and the saved fraction of the
    reusable shared-prefix tokens (acceptance: >= 0.9 after warmup — only
    the first admission must prefill the shared blocks)."""
    out = {"shared_len": shared_len, "tail_len": tail_len, "gen_len": gen_len}
    for label, enabled in (("cache_off", False), ("cache_on", True)):
        # per-mode generator with the same seed so OFF and ON admit the
        # identical prompt sequence (shared prefix included)
        traffic = _traffic(seed=7, vocab_size=vocab,
                           prompt_kind="shared_prefix",
                           shared_len=shared_len, prompt_len=tail_len)
        eng = build(enabled)
        try:
            row = run_closed_loop(eng, sp, traffic, batch, gen_len,
                                  measure_s, quantum=quantum)
            stats = dict(eng.state.prefix_stats)
            admissions = batch + row["prefills_in_window"]
            bs = eng.state.block_size
            # tokens the cache could have resolved: every admission after the
            # first can reuse the shared prefix's full blocks
            reusable = (shared_len // bs) * bs * max(0, admissions - 1)
            row.update(
                prefill_tokens_saved=stats["prefill_tokens_saved"],
                hit_rate=round(stats["hits"] / stats["lookups"], 3)
                if stats["lookups"] else 0.0,
                saved_frac_of_shared=round(
                    stats["prefill_tokens_saved"] / reusable, 3)
                if reusable else 0.0,
                evictions=stats["evictions"],
                retained_blocks=eng.state.retained_blocks)
            out[label] = row
            sys.stderr.write(f"[serving] shared_prefix {label}: {row}\n")
            tel_dir = os.environ.get("DSTPU_SERVING_TELEMETRY")
            if enabled and tel_dir:
                _dump_serving_telemetry(eng, tel_dir)
        finally:
            del eng
    return out


def _dump_serving_telemetry(eng, out_dir, job="serving_bench", spec=False,
                            extra_events=None):
    """Write the engine's Serving/prefix_cache/* counters (plus, per
    workload, Serving/spec/* or the scheduler/router series passed in
    ``extra_events``) as a TelemetryHub JSONL file for
    ``scripts/telemetry_report.py --serving``."""
    from deepspeed_tpu.monitor.monitor import JSONLMonitor

    class _Cfg:
        enabled = True
        output_path = out_dir
        job_name = job

    mon = JSONLMonitor(_Cfg())
    mon.write_events(eng.prefix_cache_events(step=0))
    if spec:
        mon.write_events(eng.spec_events(step=0))
    if extra_events:
        mon.write_events(extra_events)
    mon.close()


def run_decode_heavy(build, sp, vocab, batch, prompt_len, gen_len,
                     measure_s, pattern_len=6):
    """Decode-heavy workload (docs/serving.md): short REPETITIVE prompts
    (a ``pattern_len``-token pattern tiled to ``prompt_len`` — the
    prompt-lookup drafter's best case, standing in for quoted-context /
    multi-turn-echo traffic) and long generations, run with speculative
    decoding OFF and ON. Reports generated tok/s, per-token latency
    p50/p99, the accept-rate / tokens-per-step counters, and model forward
    passes per generated token — the number speculative decoding exists to
    shrink."""
    out = {"prompt_len": prompt_len, "gen_len": gen_len, "batch": batch}
    for label, mode in (("spec_off", False), ("spec_on", True)):
        traffic = _traffic(seed=13, vocab_size=vocab,
                           prompt_kind="repetitive", prompt_len=prompt_len,
                           pattern_len=pattern_len)
        eng = build(mode)
        try:
            row = run_closed_loop(eng, sp, traffic, batch, gen_len,
                                  measure_s, quantum=1)
            stats = dict(eng.spec_stats)
            tel_dir = os.environ.get("DSTPU_SERVING_TELEMETRY")
            if mode and tel_dir:
                _dump_serving_telemetry(eng, tel_dir,
                                        job="serving_bench_spec", spec=True)
            row["fwd_per_token"] = round(
                row["model_steps"] / max(1, row["tokens_in_window"]), 3)
            if mode:
                row["accept_rate"] = round(
                    stats["accepted_tokens"] / stats["drafted_tokens"], 3) \
                    if stats["drafted_tokens"] else 0.0
                row["tokens_per_step"] = round(
                    stats["emitted_tokens"] / stats["step_seqs"], 3) \
                    if stats["step_seqs"] else 0.0
                row["verify_steps"] = stats["verify_steps"]
                row["drafted_tokens"] = stats["drafted_tokens"]
                row["accepted_tokens"] = stats["accepted_tokens"]
            out[label] = row
            sys.stderr.write(f"[serving] decode_heavy {label}: {row}\n")
        finally:
            del eng
    return out


def run_kvquant(llama_mod, mcfg, sp, vocab, batch, prompt_len, gen_len,
                measure_s, block_size, group_size=128):
    """Quantized-KV workload (docs/serving.md "Quantized KV cache"):
    prefix cache ON, ``kv_quant`` OFF vs ON **at equal KV pool bytes** —
    the bf16 engine gets ``nb_bf16`` blocks, the int8 engine gets however
    many blocks the SAME byte budget buys once codes are int8 + fp32
    per-group scales (per-block bytes measured from the actual cache
    leaves, not assumed). Reports:

    - ``resident_ratio``: max concurrently admittable sequences at the
      byte budget, quant over bf16 — the density headline (>= 1.8x
      acceptance at group_size <= 128 on hd >= 64 models);
    - decode tok/s + ITL p50/p99 both modes (regression <= 10% accepted);
    - ``logit_mae`` / ``argmax_agree``: per-token logit MAE and greedy
      argmax agreement of the quantized forward vs bf16 on one prompt
      (direct ``apply_paged`` probe — the engines never expose logits);
    - ``greedy_identical``: fraction of greedy streams token-identical
      between the two engines on the measured workload."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import build_engine_v2

    params = llama_mod.init(mcfg, jax.random.PRNGKey(0))

    def build_eng(quant_on, nb):
        return build_engine_v2(
            llama_mod, mcfg, params,
            config={"dtype": "bfloat16",
                    "prefill_bucket": min(64, prompt_len),
                    "prefix_cache": {"enabled": True},
                    "kv_quant": {"enabled": quant_on,
                                 "group_size": group_size},
                    "ragged": {"max_tracked_sequences": batch * 4,
                               "max_ragged_batch_size": batch * 4,
                               "memory_config_blocks": nb,
                               "block_size": block_size}})

    def pool_bytes(eng):
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(eng.cache))

    def resident_capacity(eng):
        """Sequences of this workload's footprint the pool admits at once."""
        n = 0
        while eng.state.can_admit(prompt_len + gen_len) and \
                n < eng.state.max_sequences:
            eng.state.admit(10 ** 7 + n, prompt_len + gen_len)
            n += 1
        for i in range(n):
            eng.state.retire(10 ** 7 + i)
        return n

    need = (prompt_len + gen_len) // block_size + 3
    nb_bf16 = batch * need + 8
    out = {"prompt_len": prompt_len, "gen_len": gen_len, "batch": batch,
           "group_size": group_size, "block_size": block_size}
    eng_off = build_eng(False, nb_bf16)
    per_block_bf16 = pool_bytes(eng_off) // (nb_bf16)
    budget = nb_bf16 * per_block_bf16
    # how many int8+scales blocks the SAME bytes buy (measure, don't assume)
    probe = build_eng(True, nb_bf16)
    per_block_q = pool_bytes(probe) // nb_bf16
    del probe
    nb_q = int(budget // per_block_q)
    out["pool_bytes"] = int(budget)
    out["blocks"] = {"bf16": nb_bf16, "int8": nb_q}
    eng_on = build_eng(True, nb_q)
    out["resident_seqs"] = {"bf16": resident_capacity(eng_off),
                            "int8": resident_capacity(eng_on)}
    out["resident_ratio"] = round(
        out["resident_seqs"]["int8"] / max(1, out["resident_seqs"]["bf16"]),
        2)

    streams = {}
    for label, eng in (("quant_off", eng_off), ("quant_on", eng_on)):
        traffic = _traffic(seed=31, vocab_size=vocab, prompt_len=prompt_len)
        row = run_closed_loop(eng, sp, traffic, batch, gen_len, measure_s,
                              quantum=1)
        out[label] = row
        # greedy stream comparison on a fixed prompt set (outside the
        # measured window)
        grng = np.random.default_rng(77)
        prompts = [grng.integers(0, vocab, prompt_len).tolist()
                   for _ in range(min(batch, 4))]
        streams[label] = eng.generate(prompts, max_new_tokens=gen_len,
                                      seed=0)
        if label == "quant_on":
            eng.debug_check_cache()
            eng.state.debug_check()
            tel_dir = os.environ.get("DSTPU_SERVING_TELEMETRY")
            if tel_dir:
                _dump_serving_telemetry(eng, tel_dir,
                                        job="serving_bench_kvquant")
        sys.stderr.write(f"[serving] kvquant {label}: {row}\n")
    out["greedy_identical"] = round(
        sum(a == b for a, b in zip(streams["quant_off"],
                                   streams["quant_on"]))
        / max(1, len(streams["quant_off"])), 3)
    out["decode_tok_s_ratio"] = round(
        out["quant_on"]["tok_per_sec"]
        / max(1e-9, out["quant_off"]["tok_per_sec"]), 3)
    del eng_off, eng_on

    # per-token logit error probe: one prompt through apply_paged on a
    # bf16 cache vs an int8+scales cache (identical tables/positions)
    import jax.numpy as jnp
    prng = np.random.default_rng(5)
    toks = jnp.asarray(prng.integers(0, vocab, (1, prompt_len)), jnp.int32)
    nb_p = prompt_len // block_size + 3
    tables = jnp.arange(1, nb_p + 1, dtype=jnp.int32)[None]
    ctx = jnp.zeros((1,), jnp.int32)
    c_bf = llama_mod.init_paged_cache(mcfg, nb_p + 2, block_size)
    c_q = llama_mod.init_paged_cache(mcfg, nb_p + 2, block_size,
                                     kv_quant_group=group_size)
    lo_bf, _ = llama_mod.apply_paged(mcfg, params, toks, c_bf, tables, ctx)
    lo_q, _ = llama_mod.apply_paged(mcfg, params, toks, c_q, tables, ctx)
    out["logit_mae"] = round(float(jnp.mean(jnp.abs(lo_q - lo_bf))), 5)
    out["argmax_agree"] = round(float(jnp.mean(
        (jnp.argmax(lo_q, -1) == jnp.argmax(lo_bf, -1)))), 3)
    return out


def run_open_loop(build, sp, vocab, rate_rps, duration_s, prompt_len,
                  gen_len, slo_ms, quantum=1):
    """Open-loop Poisson workload (docs/serving.md "Scheduler & router"):
    one seeded arrival trace replayed against (a) the continuous-batching
    SCHEDULER and (b) the hand-rolled FCFS admit/step loop this bench used
    before the scheduler existed. Identical traffic, identical engine
    config — the delta is pure scheduling policy. Reports, per mode:
    goodput-under-SLO (requests completed within their e2e deadline, as a
    rate and a fraction of completions), queue-wait p50/p99, and the
    scheduler's preemption count."""
    import collections

    from deepspeed_tpu.inference.serving import (SchedulerConfig,
                                                 ServingScheduler)
    import numpy as np

    def warm(eng, max_batch):
        hi = prompt_len if isinstance(prompt_len, int) else prompt_len[1]
        _warm_engine(eng, sp, vocab, (hi,), max_batch, quantum=quantum)

    traffic = _traffic(seed=11, vocab_size=vocab, process="poisson",
                       rate_rps=rate_rps, prompt_len=prompt_len,
                       gen_len=gen_len, deadline_ms=slo_ms)
    arrivals = traffic.arrivals(duration_s)
    out = {"arrivals": len(arrivals), "rate_rps": rate_rps,
           "duration_s": duration_s, "slo_ms": slo_ms,
           "prompt_len": list(prompt_len) if not isinstance(prompt_len, int)
           else prompt_len,
           "gen_len": list(gen_len) if not isinstance(gen_len, int)
           else gen_len}
    if not arrivals:
        return out
    time_cap = duration_s * 10 + 60

    def summary(elapsed, e2e_met_tok, qwaits_ms, extra=None):
        done = len(e2e_met_tok)
        met = [r for r in e2e_met_tok if r[1]]
        qw = np.asarray(qwaits_ms) if qwaits_ms else np.zeros((1,))
        row = {"completed": done, "slo_met": len(met),
               "goodput_rps": round(len(met) / elapsed, 2),
               "goodput_frac": round(len(met) / done, 3) if done else 0.0,
               "goodput_tok_per_sec": round(
                   sum(r[2] for r in met) / elapsed, 1),
               "queue_wait_ms": {
                   "p50": round(float(np.percentile(qw, 50)), 2),
                   "p99": round(float(np.percentile(qw, 99)), 2)}}
        row.update(extra or {})
        return row

    # --- scheduler ON ------------------------------------------------- #
    eng = build()
    sched = ServingScheduler(eng, SchedulerConfig(decode_quantum=quantum))
    warm(eng, eng.state.max_sequences)
    handles = []
    i = 0
    t0 = time.perf_counter()
    while i < len(arrivals) or sched.pending:
        now = time.perf_counter() - t0
        if now > time_cap:
            break
        while i < len(arrivals) and arrivals[i].t <= now:
            handles.append(sched.submit(arrivals[i].request))
            i += 1
        if not sched.pending:
            if i < len(arrivals):
                time.sleep(min(max(arrivals[i].t - now, 0.0), 0.05))
            continue
        sched.tick()
    elapsed = time.perf_counter() - t0
    rows = [(h.e2e_ms, bool(h.slo_met), len(h.tokens))
            for h in handles if h.state == "done"]
    out["scheduler"] = summary(
        elapsed, rows, [h.queue_wait_ms for h in handles
                        if h.queue_wait_ms is not None],
        extra={"preempted": sched.stats["preempted"],
               "resumed": sched.stats["resumed"],
               "chunked_admissions": sched.stats["chunked_admissions"]})
    sys.stderr.write(f"[serving] open_loop scheduler: {out['scheduler']}\n")
    tel_dir = os.environ.get("DSTPU_SERVING_TELEMETRY")
    if tel_dir:
        _dump_serving_telemetry(eng, tel_dir, job="serving_bench_sched",
                                extra_events=sched.sched_events(step=0))
    del sched, eng

    # --- hand-rolled FCFS baseline (the pre-scheduler pattern) --------- #
    eng = build()
    warm(eng, 1)                 # the FCFS loop only ever admits one-by-one
    fifo = collections.deque()   # (arrival, arrival-observed wall time)
    live = {}                    # uid → {sub, max_new, deadline}
    results = []                 # (e2e_ms, met, tokens)
    qwaits = []
    i = 0
    next_uid = 0
    t0 = time.perf_counter()
    while i < len(arrivals) or fifo or live:
        now = time.perf_counter() - t0
        if now > time_cap:
            break
        while i < len(arrivals) and arrivals[i].t <= now:
            fifo.append((arrivals[i], now))
            i += 1
        while fifo and eng.state.can_admit(len(fifo[0][0].request.prompt)):
            arr, t_sub = fifo.popleft()
            uid = next_uid
            next_uid += 1
            eng.put(uid, arr.request.prompt, sp, seed=uid)
            qwaits.append((time.perf_counter() - t0 - t_sub) * 1e3)
            live[uid] = {"sub": t_sub,
                         "max_new": arr.request.max_new_tokens,
                         "deadline": arr.request.deadline_ms}
        if not live:
            if i < len(arrivals):
                now = time.perf_counter() - t0
                time.sleep(min(max(arrivals[i].t - now, 0.0), 0.05))
            continue
        if quantum > 1:
            eng.step_many(quantum, sp)
        else:
            eng.step(sp)
        for uid in list(live):
            d = eng.state.seqs.get(uid)
            if d is not None and len(d.generated) >= live[uid]["max_new"]:
                eng.finish(uid)
                info = live.pop(uid)
                e2e = (time.perf_counter() - t0 - info["sub"]) * 1e3
                results.append((e2e, e2e <= info["deadline"],
                                info["max_new"]))
    elapsed = time.perf_counter() - t0
    for d in list(eng.state.seqs.values()):
        eng.finish(d.uid)
    out["hand_rolled"] = summary(elapsed, results, qwaits)
    sys.stderr.write(
        f"[serving] open_loop hand_rolled: {out['hand_rolled']}\n")
    del eng
    return out


def run_chaos(build, sp, vocab, rate_rps, duration_s, prompt_len, gen_len,
              slo_ms):
    """``detail.chaos`` (docs/serving.md "Fleet fault tolerance"): one seeded
    open-loop Poisson trace served by a TWO-replica fleet with the
    ``serving.fleet`` block enabled, run fault-free and again with a
    mid-trace replica crash + recovery (``testing.faults.replica_crash``
    covering ~20% of the trace). Reports per mode: goodput-under-SLO,
    queue-wait p99, lost requests (must be 0 — every request reaches a
    terminal state), and the failover / circuit-breaker counters; the
    headline is the fault-free goodput delta — what one replica crash costs
    once failover and breaker re-admission do their jobs."""
    import numpy as np

    from deepspeed_tpu.inference.serving import (FleetConfig, ReplicaRouter,
                                                 RouterConfig,
                                                 SchedulerConfig,
                                                 ServingScheduler)
    from deepspeed_tpu.testing.faults import replica_crash

    out = {"rate_rps": rate_rps, "duration_s": duration_s, "slo_ms": slo_ms,
           "replicas": 2}
    time_cap = duration_s * 10 + 60
    for label, crash in (("fault_free", False), ("with_crash", True)):
        # per-mode generator with the same seed: both modes see the
        # identical arrival trace — the delta is pure fault handling
        traffic = _traffic(seed=17, vocab_size=vocab, process="poisson",
                           rate_rps=rate_rps, prompt_len=prompt_len,
                           gen_len=gen_len, deadline_ms=slo_ms)
        arrivals = traffic.arrivals(duration_s)
        scheds = [ServingScheduler(build(),
                                   SchedulerConfig(max_admissions_per_tick=4))
                  for _ in range(2)]
        router = ReplicaRouter(scheds, RouterConfig(fleet=FleetConfig(
            enabled=True, failure_threshold=1, probe_backoff_ticks=25)))
        hi = prompt_len if isinstance(prompt_len, int) else prompt_len[1]
        ghi = gen_len if isinstance(gen_len, int) else gen_len[1]
        for s in scheds:        # prefill bursts n=1,2,4 + failover-replay
            _warm_engine(s.engine, sp, vocab, (hi, hi + ghi), 4)
        handles = []
        i = 0
        crash_cm = None
        crashed = False
        crash_steps_left = 0
        t0 = time.perf_counter()
        while i < len(arrivals) or router.pending:
            now = time.perf_counter() - t0
            if now > time_cap:
                break
            while i < len(arrivals) and arrivals[i].t <= now:
                handles.append(router.submit(arrivals[i].request))
                i += 1
            # mid-trace crash: replica 0 dies once half the arrivals are in,
            # stays dead for a fixed number of router steps, then recovers
            # (the breaker's half-open probe re-admits it)
            if crash and not crashed and i >= len(arrivals) // 2:
                crash_cm = replica_crash(scheds[0])
                crash_cm.__enter__()
                crashed = True
                crash_steps_left = 40
            if crash_cm is not None:
                crash_steps_left -= 1
                if crash_steps_left <= 0:
                    crash_cm.__exit__(None, None, None)  # replica recovers
                    crash_cm = None
            if not router.pending:
                if i < len(arrivals):
                    time.sleep(min(max(arrivals[i].t - now, 0.0), 0.05))
                continue
            router.step()
        if crash_cm is not None:
            crash_cm.__exit__(None, None, None)
        while router.pending and time.perf_counter() - t0 < time_cap:
            router.step()                     # breaker probes need idle steps
        elapsed = time.perf_counter() - t0
        done = [h for h in handles if h.state == "done"]
        met = [h for h in done if h.slo_met]
        qw = np.asarray([h.queue_wait_ms for h in handles
                         if h.queue_wait_ms is not None] or [0.0])
        fs = router.fleet_stats
        row = {"arrivals": len(handles), "completed": len(done),
               "slo_met": len(met),
               "goodput_rps": round(len(met) / elapsed, 2),
               "goodput_frac": round(len(met) / len(done), 3)
               if done else 0.0,
               "queue_wait_p99_ms": round(float(np.percentile(qw, 99)), 2),
               "lost_requests": sum(1 for h in handles if not h.done),
               "failovers": fs["failovers"],
               "replayed_tokens": fs["replayed_tokens"],
               "shed_requests": fs["shed_requests"],
               "circuit_open": fs["circuit_open"],
               "circuit_closed": fs["circuit_closed"]}
        out[label] = row
        sys.stderr.write(f"[serving] chaos {label}: {row}\n")
        tel_dir = os.environ.get("DSTPU_SERVING_TELEMETRY")
        if crash and tel_dir:
            _dump_serving_telemetry(
                scheds[0].engine, tel_dir, job="serving_bench_fleet",
                extra_events=router.fleet_events(step=0)
                + router.router_events(step=0))
        del router, scheds
    ff, wc = out.get("fault_free"), out.get("with_crash")
    if isinstance(ff, dict) and isinstance(wc, dict):
        # the headline: goodput a crash costs AFTER failover does its job
        out["goodput_frac_delta"] = round(
            ff["goodput_frac"] - wc["goodput_frac"], 3)
    return out


def run_disagg(build, sp, vocab, rate_rps, duration_s, prompt_len, gen_len,
               slo_ms, replicas=3, num_prefill=1):
    """``detail.disagg`` (docs/serving.md "Disaggregated prefill/decode"):
    one seeded fleet-shaped open-loop trace — diurnal rate modulation with
    a burst overlay, heavy-tailed multi-turn sessions, and a weighted
    tenant mix, the million-user shape compressed onto a bench timescale —
    served twice on the SAME ``replicas`` engines: a monolithic fleet vs
    ``num_prefill`` prefill + the rest decode with the chain-hash-keyed KV
    handoff ON. Equal chips, identical first-turn traffic; the delta is
    pure tier separation (decode ticks that never share a step budget with
    a prefill). Reports per mode: goodput-under-SLO, TTFT p50/p99,
    queue-wait p99 — plus the disagg arm's wire accounting (handoffs, wire
    vs bf16-equivalent bytes and ratio, chain-hash dedup savings)."""
    import numpy as np

    from deepspeed_tpu.inference.serving import (DisaggConfig, ReplicaRouter,
                                                 RouterConfig,
                                                 SchedulerConfig,
                                                 ServingScheduler)

    out = {"rate_rps": rate_rps, "duration_s": duration_s, "slo_ms": slo_ms,
           "replicas": replicas, "num_prefill": num_prefill}
    time_cap = duration_s * 10 + 60
    for label, disagg_on in (("monolithic", False), ("disagg", True)):
        # per-mode generator with the same seed: identical first-turn
        # arrivals; follow-up turns chain off each mode's own completions
        traffic = _traffic(seed=29, vocab_size=vocab, process="diurnal",
                           rate_rps=rate_rps, diurnal_amplitude=0.6,
                           diurnal_period_s=duration_s, burst_overlay=True,
                           burst_size=3, burst_interval_s=duration_s / 4,
                           prompt_len=prompt_len, gen_len=gen_len,
                           turns_dist="lognormal", turns_mu=0.3,
                           turns_sigma=0.8, max_turns=4, followup_len=4,
                           tenant_mix=(("free", 6.0, 1), ("pro", 3.0, 0),
                                       ("enterprise", 1.0, 0)),
                           deadline_ms=slo_ms)
        arrivals = traffic.arrivals(duration_s)
        scheds = [ServingScheduler(build(),
                                   SchedulerConfig(max_admissions_per_tick=4))
                  for _ in range(replicas)]
        router = ReplicaRouter(scheds, RouterConfig(
            disagg=DisaggConfig(enabled=disagg_on, num_prefill=num_prefill)))
        hi = prompt_len if isinstance(prompt_len, int) else prompt_len[1]
        ghi = gen_len if isinstance(gen_len, int) else gen_len[1]
        for s in scheds:
            _warm_engine(s.engine, sp, vocab, (hi, hi + ghi), 4)
        handles = []          # (arrival, handle, ttft_box)
        followups = []        # arrivals whose predecessor turn completed
        ttfts = []
        i = 0
        t0 = time.perf_counter()

        def _submit(arr):
            box = []
            h = router.submit(
                arr.request,
                on_token=lambda _t, _b=box: _b.append(
                    time.perf_counter()) if not _b else None)
            handles.append((arr, h, box))
            return h

        while i < len(arrivals) or followups or router.pending:
            now = time.perf_counter() - t0
            if now > time_cap:
                break
            while i < len(arrivals) and arrivals[i].t <= now:
                _submit(arrivals[i])
                i += 1
            while followups and followups[0].t <= now:
                _submit(followups.pop(0))
            # chain the next session turn off each freshly completed turn
            for arr, h, _ in handles:
                if h.state == "done" and not getattr(h, "_chained", False):
                    h._chained = True
                    nxt = traffic.followup(arr, h.tokens, now_s=now)
                    if nxt is not None:
                        followups.append(nxt)
            followups.sort(key=lambda a: a.t)
            if not router.pending:
                pend = [a.t for a in followups]
                if i < len(arrivals):
                    pend.append(arrivals[i].t)
                if pend:
                    now = time.perf_counter() - t0
                    time.sleep(min(max(min(pend) - now, 0.0), 0.05))
                    continue
                if not any(h.state == "done" and not getattr(
                        h, "_chained", False) for _, h, _ in handles):
                    break
                continue
            router.step()
        elapsed = time.perf_counter() - t0
        done = [h for _, h, _ in handles if h.state == "done"]
        met = [h for h in done if h.slo_met]
        ttfts = [(b[0] - t0 - a.t) * 1e3 for a, h, b in handles
                 if b and h._submit_t is not None]
        tt = np.asarray(ttfts or [0.0])
        qw = np.asarray([h.queue_wait_ms for _, h, _ in handles
                         if h.queue_wait_ms is not None] or [0.0])
        row = {"requests": len(handles), "first_turns": len(arrivals),
               "completed": len(done), "slo_met": len(met),
               "goodput_rps": round(len(met) / elapsed, 2),
               "goodput_frac": round(len(met) / len(done), 3)
               if done else 0.0,
               "ttft_p50_ms": round(float(np.percentile(tt, 50)), 2),
               "ttft_p99_ms": round(float(np.percentile(tt, 99)), 2),
               "queue_wait_p99_ms": round(float(np.percentile(qw, 99)), 2)}
        if disagg_on:
            ds = router.disagg_stats
            row["handoffs"] = ds["handoffs"]
            row["blocks_shipped"] = ds["blocks_shipped"]
            row["wire_bytes"] = ds["wire_bytes"]
            row["bf16_equiv_bytes"] = ds["bf16_equiv_bytes"]
            row["wire_ratio"] = round(
                ds["wire_bytes"] / ds["bf16_equiv_bytes"], 3) \
                if ds["bf16_equiv_bytes"] else 0.0
            row["dedup_blocks"] = ds["dedup_blocks"]
            row["dedup_bytes_saved"] = ds["dedup_bytes_saved"]
            row["handoff_fallbacks"] = ds["handoff_fallbacks"]
            tel_dir = os.environ.get("DSTPU_SERVING_TELEMETRY")
            if tel_dir:
                _dump_serving_telemetry(
                    scheds[0].engine, tel_dir, job="serving_bench_disagg",
                    extra_events=router.disagg_events(step=0)
                    + router.router_events(step=0))
        out[label] = row
        sys.stderr.write(f"[serving] disagg {label}: {row}\n")
        del router, scheds
    mono, dis = out.get("monolithic"), out.get("disagg")
    if isinstance(mono, dict) and isinstance(dis, dict):
        # the headline: what tier separation buys at equal chip count
        out["goodput_frac_delta"] = round(
            dis["goodput_frac"] - mono["goodput_frac"], 3)
        out["ttft_p99_delta_ms"] = round(
            dis["ttft_p99_ms"] - mono["ttft_p99_ms"], 2)
    return out


def run_multitenant(build, sp, vocab, duration_s, prompt_len, gen_len,
                    slo_ms_by_tenant, rate_by_tenant):
    """``detail.multitenant`` (docs/observability.md "Fleet observability"):
    a seeded two-tenant open-loop overload probe on a TWO-replica fleet
    with the ``serving.obs`` plane enabled. Each tenant has its own arrival
    rate and SLO over the seeded ``TrafficGenerator``; the row reports
    per-tenant goodput-under-SLO and the burn-rate alert count — on a
    healthy run exactly the SLO-violating tenant alerts."""
    from deepspeed_tpu.inference.serving import (FleetObsConfig,
                                                 ReplicaRouter, RouterConfig,
                                                 SchedulerConfig,
                                                 ServingScheduler)

    out = {"duration_s": duration_s, "replicas": 2,
           "slo_ms": dict(slo_ms_by_tenant), "rate_rps": dict(rate_by_tenant)}
    time_cap = duration_s * 10 + 60
    arrivals = []
    for k, (tenant, slo_ms) in enumerate(sorted(slo_ms_by_tenant.items())):
        traffic = _traffic(seed=29 + k, vocab_size=vocab, process="poisson",
                           rate_rps=rate_by_tenant[tenant],
                           prompt_len=prompt_len, gen_len=gen_len,
                           deadline_ms=slo_ms, tenant=tenant)
        arrivals.extend(traffic.arrivals(duration_s))
    arrivals.sort(key=lambda a: a.t)
    scheds = [ServingScheduler(build(),
                               SchedulerConfig(max_admissions_per_tick=4))
              for _ in range(2)]
    router = ReplicaRouter(scheds, RouterConfig(obs=FleetObsConfig(
        enabled=True, burn_fast_window_s=max(duration_s, 5.0),
        burn_slow_window_s=max(duration_s * 4, 20.0), burn_threshold=2.0,
        default_slo_target=0.9)))
    hi = prompt_len if isinstance(prompt_len, int) else prompt_len[1]
    ghi = gen_len if isinstance(gen_len, int) else gen_len[1]
    for s in scheds:
        _warm_engine(s.engine, sp, vocab, (hi, hi + ghi), 4)
    handles = []
    i = 0
    t0 = time.perf_counter()
    while i < len(arrivals) or router.pending:
        now = time.perf_counter() - t0
        if now > time_cap:
            break
        while i < len(arrivals) and arrivals[i].t <= now:
            handles.append(router.submit(arrivals[i].request))
            i += 1
        if not router.pending:
            if i < len(arrivals):
                time.sleep(min(max(arrivals[i].t - now, 0.0), 0.05))
            continue
        router.step()
    events = router.fleet_obs_events(step=0)
    acc = router.obs.accountant
    out["tenants"] = {t: {k: round(v, 3) for k, v in row.items()}
                      for t, row in acc.tenant_summary().items()}
    out["burn_alerts"] = len(acc.alerts)
    out["alerted_tenants"] = sorted({a["tenant"] for a in acc.alerts})
    out["traced_requests"] = router.obs.stats["traced_requests"]
    out["lost_requests"] = sum(1 for h in handles if not h.done)
    sys.stderr.write(f"[serving] multitenant: {out}\n")
    tel_dir = os.environ.get("DSTPU_SERVING_TELEMETRY")
    if tel_dir:
        _dump_serving_telemetry(
            scheds[0].engine, tel_dir, job="serving_bench_fleetobs",
            extra_events=events + router.router_events(step=0))
    del router, scheds
    return out


def run_longprompt_probe(build, sp, vocab, rng, batch, short_len, long_len,
                         chunk, n_steps=24):
    """Head-of-line blocking (the FastGen Dynamic-SplitFuse motivation):
    ``batch`` short clients decode steadily; a LONG prompt is admitted
    mid-stream. Per step-call wall times show how long the live decodes
    stall — one-shot prefill stalls for the whole prompt, split admission
    for at most one chunk. Returns {mode: {p50/p95/worst step ms}}."""
    import numpy as np

    out = {}
    for split in (0, chunk):
        eng = build(split)
        for u in range(batch):
            eng.put(u, rng.integers(0, vocab, (short_len,),
                                    dtype=np.int32).tolist(), sp, seed=u)
        eng.step(sp)  # warm the decode program
        long_prompt = rng.integers(0, vocab, (long_len,),
                                   dtype=np.int32).tolist()
        # warm the admission path's COMPILES outside the measured steps: a
        # throwaway long sequence runs the one-shot prefill / every chunk
        # variant once, then retires
        if split:
            eng.put_split(9998, long_prompt, sp)
            while 9998 in eng._pending_prefill:
                eng.step(sp)
        else:
            eng.put(9998, long_prompt, sp, seed=98)
        eng.finish(9998)
        if split:
            eng.put_split(9999, long_prompt, sp)
        call_ms = []
        for i in range(n_steps):
            if not split and i == 2:
                t0 = time.perf_counter()
                eng.put(9999, long_prompt, sp, seed=99)
                call_ms.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            eng.step(sp)
            call_ms.append((time.perf_counter() - t0) * 1e3)
        for d in list(eng.state.seqs.values()):
            eng.finish(d.uid)
        del eng
        arr = np.asarray(call_ms)
        out["split_%d" % split if split else "one_shot"] = {
            "p50_ms": round(float(np.percentile(arr, 50)), 2),
            "worst_ms": round(float(arr.max()), 2),
            "long_len": long_len, "chunk": chunk or long_len}
    return out


def main():
    import numpy as np
    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from deepspeed_tpu.inference.engine_v2 import build_engine_v2
    from deepspeed_tpu.inference.sampling import SamplingParams
    from deepspeed_tpu.models import llama

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    RESULT["detail"]["backend"] = backend
    if on_tpu:
        # the bench model (235M, hd=128) at serving-realistic lengths
        mcfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=3584,
            num_layers=12, num_heads=8, num_kv_heads=4, max_seq_len=2048,
            rope_theta=500000.0)
        prompt_len, gen_len, measure_s = 512, 128, 20.0
        batches = [8, 16, 32]
    else:
        mcfg = llama.LlamaConfig.tiny()
        prompt_len, gen_len, measure_s = 32, 8, 5.0
        batches = [4, 8]
    rng = np.random.default_rng(0)
    sp = SamplingParams(greedy=True)
    rows = {}
    RESULT["detail"]["rows"] = rows
    best = 0.0
    # DSTPU_SERVING_TRACE=<out.json>: run ONE configuration with the span
    # tracer on and dump its flight recorder as a Perfetto/Chrome trace +
    # latency SLO percentiles
    trace_path = os.environ.get("DSTPU_SERVING_TRACE")
    traced = False
    for batch in batches:
        for quantum in (1, 8):
            eng = None
            label = f"{batch}clients_q{quantum}"
            try:
                cfg_dict = {"dtype": "bfloat16",
                            "prefill_bucket": prompt_len,
                            "ragged": {
                                "max_tracked_sequences": batch,
                                "max_ragged_batch_size": batch,
                                "memory_config_blocks":
                                    batch * ((prompt_len + gen_len) // 32 + 3)
                                    + 8,
                                "block_size": 32}}
                want_trace = bool(trace_path) and not traced
                if want_trace:
                    cfg_dict["trace"] = {"enabled": True, "ring_size": 16384,
                                         "dump_on_crash": False}
                eng = build_engine_v2(
                    llama, mcfg, llama.init(mcfg, jax.random.PRNGKey(0)),
                    config=cfg_dict)
                row = run_closed_loop(
                    eng, sp, _traffic(seed=0, vocab_size=mcfg.vocab_size,
                                      prompt_len=prompt_len),
                    batch, gen_len, measure_s, quantum=quantum)
                row.update(prompt_len=prompt_len, gen_len=gen_len)
                rows[label] = row
                tps = row["tok_per_sec"]
                if want_trace:
                    eng.export_trace(trace_path)
                    rows[label]["latency_slo"] = {
                        m: {k: round(v, 3) for k, v in s.items()}
                        for m, s in eng.latency_summary().items()}
                    RESULT["detail"]["trace_path"] = trace_path
                    traced = True
                best = max(best, tps)
                sys.stderr.write(f"[serving] {label}: {rows[label]}\n")
            except Exception as e:
                rows[label] = f"error: {str(e)[-200:]}"
            finally:
                del eng  # free HBM before the next configuration
    RESULT["value"] = round(best, 1)

    # shared-system-prompt workload: prefix-cache ON vs OFF (docs/serving.md)
    try:
        if on_tpu:
            batch_sp, shared_sp, tail_sp, gen_sp, meas_sp, q_sp = \
                16, 448, 64, 128, 20.0, 8
            bs_sp = 32
        else:
            batch_sp, shared_sp, tail_sp, gen_sp, meas_sp, q_sp = \
                4, 64, 16, 8, 5.0, 1
            bs_sp = 16

        def build_sp(prefix_on):
            nb = (batch_sp + 1) * ((shared_sp + tail_sp + gen_sp) // bs_sp
                                   + 3) + 8
            return build_engine_v2(
                llama, mcfg, llama.init(mcfg, jax.random.PRNGKey(0)),
                config={"dtype": "bfloat16",
                        "prefill_bucket": min(64, shared_sp),
                        "prefix_cache": {"enabled": prefix_on},
                        "ragged": {"max_tracked_sequences": batch_sp,
                                   "max_ragged_batch_size": batch_sp,
                                   "memory_config_blocks": nb,
                                   "block_size": bs_sp}})

        RESULT["detail"]["shared_prefix"] = run_shared_prefix(
            build_sp, sp, mcfg.vocab_size, batch_sp, shared_sp, tail_sp,
            gen_sp, meas_sp, quantum=q_sp)
    except Exception as e:
        RESULT["detail"]["shared_prefix"] = f"error: {str(e)[-200:]}"

    # decode-heavy workload: speculative decoding ON vs OFF (docs/serving.md)
    # — short repetitive prompts, long generations; records the decode
    # trajectory (tok/s, accept rate, ITL p50/p99, fwd passes per token) for
    # the silicon rounds (BENCH_r06.json onward)
    try:
        if on_tpu:
            batch_sd, plen_sd, glen_sd, meas_sd, k_sd = 16, 64, 256, 20.0, 6
            bs_sd = 32
        else:
            batch_sd, plen_sd, glen_sd, meas_sd, k_sd = 4, 24, 16, 5.0, 4
            bs_sd = 16

        def build_sd(spec_mode):
            nb = (batch_sd + 1) * ((plen_sd + glen_sd) // bs_sd + 3) + 8
            return build_engine_v2(
                llama, mcfg, llama.init(mcfg, jax.random.PRNGKey(0)),
                config={"dtype": "bfloat16",
                        "prefill_bucket": min(64, plen_sd),
                        "speculative": {"enabled": bool(spec_mode),
                                        "max_draft_tokens": k_sd},
                        "ragged": {"max_tracked_sequences": batch_sd,
                                   "max_ragged_batch_size": batch_sd,
                                   "memory_config_blocks": nb,
                                   "block_size": bs_sd}})

        RESULT["detail"]["decode_heavy"] = run_decode_heavy(
            build_sd, sp, mcfg.vocab_size, batch_sd, plen_sd, glen_sd,
            meas_sd)
    except Exception as e:
        RESULT["detail"]["decode_heavy"] = f"error: {str(e)[-200:]}"

    # quantized-KV workload: prefix cache ON, kv_quant OFF vs ON at EQUAL
    # pool bytes — resident sequences, decode tok/s, ITL p50/p99, per-token
    # logit MAE (docs/serving.md "Quantized KV cache"); gated by
    # DSTPU_BENCH_KVQUANT=0
    if os.environ.get("DSTPU_BENCH_KVQUANT", "1") != "0":
        try:
            if on_tpu:
                mcfg_kq = mcfg          # 235M, hd=128
                batch_kq, plen_kq, glen_kq, meas_kq, bs_kq = \
                    16, 256, 64, 20.0, 32
            else:
                # hd=64 (not tiny's 16): the fp32 scale sidecar is 4/hd of
                # the code bytes, so small heads understate the density win
                # the serving models (hd >= 64) actually get
                mcfg_kq = llama.LlamaConfig(
                    vocab_size=512, hidden_size=128, intermediate_size=256,
                    num_layers=2, num_heads=2, num_kv_heads=2,
                    max_seq_len=512)
                batch_kq, plen_kq, glen_kq, meas_kq, bs_kq = \
                    4, 32, 8, 5.0, 16
            RESULT["detail"]["kvquant"] = run_kvquant(
                llama, mcfg_kq, sp, mcfg_kq.vocab_size, batch_kq, plen_kq,
                glen_kq, meas_kq, bs_kq)
        except Exception as e:
            RESULT["detail"]["kvquant"] = f"error: {str(e)[-200:]}"

    # open-loop Poisson workload: continuous-batching scheduler vs the
    # hand-rolled FCFS loop on the SAME seeded arrival trace — goodput under
    # SLO, queue-wait percentiles, preemption counts (docs/serving.md)
    try:
        if on_tpu:
            rate_ol, dur_ol, plen_ol, glen_ol, slo_ol, q_ol = \
                24.0, 20.0, (64, 256), (32, 96), 4000.0, 4
            slots_ol, bs_ol = 16, 32
        else:
            rate_ol, dur_ol, plen_ol, glen_ol, slo_ol, q_ol = \
                20.0, 5.0, (16, 32), (4, 10), 2500.0, 1
            slots_ol, bs_ol = 8, 16
        max_tok_ol = plen_ol[1] + glen_ol[1]

        def build_ol():
            nb = slots_ol * ((max_tok_ol + bs_ol - 1) // bs_ol + 3) + 8
            return build_engine_v2(
                llama, mcfg, llama.init(mcfg, jax.random.PRNGKey(0)),
                config={"dtype": "bfloat16",
                        "prefill_bucket": min(64, plen_ol[1]),
                        "prefix_cache": {"enabled": True},
                        "ragged": {"max_tracked_sequences": slots_ol,
                                   "max_ragged_batch_size": slots_ol,
                                   "memory_config_blocks": nb,
                                   "block_size": bs_ol}})

        RESULT["detail"]["open_loop"] = run_open_loop(
            build_ol, sp, mcfg.vocab_size, rate_ol, dur_ol, plen_ol,
            glen_ol, slo_ol, quantum=q_ol)
    except Exception as e:
        RESULT["detail"]["open_loop"] = f"error: {str(e)[-200:]}"

    # fleet chaos probe: goodput-under-SLO and queue-wait p99 with vs
    # without a mid-trace replica crash on a two-replica fleet — the
    # failover / circuit-breaker trajectory row (docs/serving.md "Fleet
    # fault tolerance")
    try:
        if on_tpu:
            rate_ch, dur_ch, plen_ch, glen_ch, slo_ch = \
                16.0, 16.0, (64, 192), (16, 48), 4000.0
            slots_ch, bs_ch = 12, 32
        else:
            rate_ch, dur_ch, plen_ch, glen_ch, slo_ch = \
                16.0, 4.0, (12, 24), (3, 8), 2500.0
            slots_ch, bs_ch = 6, 16
        max_tok_ch = plen_ch[1] + glen_ch[1]

        def build_ch():
            nb = slots_ch * ((max_tok_ch + bs_ch - 1) // bs_ch + 3) + 8
            return build_engine_v2(
                llama, mcfg, llama.init(mcfg, jax.random.PRNGKey(0)),
                config={"dtype": "bfloat16",
                        "prefill_bucket": min(64, plen_ch[1]),
                        "prefix_cache": {"enabled": True},
                        "ragged": {"max_tracked_sequences": slots_ch,
                                   "max_ragged_batch_size": slots_ch,
                                   "memory_config_blocks": nb,
                                   "block_size": bs_ch}})

        RESULT["detail"]["chaos"] = run_chaos(
            build_ch, sp, mcfg.vocab_size, rate_ch, dur_ch, plen_ch,
            glen_ch, slo_ch)
    except Exception as e:
        RESULT["detail"]["chaos"] = f"error: {str(e)[-200:]}"

    # disaggregated prefill/decode probe: equal-chip monolithic vs two-tier
    # fleet on one seeded diurnal/heavy-tail/multi-tenant trace — goodput
    # under SLO, TTFT p99, and the KV-handoff wire accounting
    # (docs/serving.md "Disaggregated prefill/decode"); gated by
    # DSTPU_BENCH_DISAGG=0
    if os.environ.get("DSTPU_BENCH_DISAGG", "1") != "0":
        try:
            if on_tpu:
                rate_dg, dur_dg, plen_dg, glen_dg, slo_dg = \
                    18.0, 16.0, (64, 192), (16, 48), 4000.0
                slots_dg, bs_dg = 12, 32
            else:
                rate_dg, dur_dg, plen_dg, glen_dg, slo_dg = \
                    12.0, 4.0, (12, 24), (3, 8), 2500.0
                slots_dg, bs_dg = 6, 16
            max_tok_dg = plen_dg[1] + glen_dg[1] * 4  # multi-turn histories

            def build_dg():
                nb = slots_dg * ((max_tok_dg + bs_dg - 1) // bs_dg + 3) + 8
                return build_engine_v2(
                    llama, mcfg, llama.init(mcfg, jax.random.PRNGKey(0)),
                    config={"dtype": "bfloat16",
                            "prefill_bucket": min(64, plen_dg[1]),
                            "prefix_cache": {"enabled": True},
                            "ragged": {"max_tracked_sequences": slots_dg,
                                       "max_ragged_batch_size": slots_dg,
                                       "memory_config_blocks": nb,
                                       "block_size": bs_dg}})

            RESULT["detail"]["disagg"] = run_disagg(
                build_dg, sp, mcfg.vocab_size, rate_dg, dur_dg, plen_dg,
                glen_dg, slo_dg, replicas=3, num_prefill=1)
        except Exception as e:
            RESULT["detail"]["disagg"] = f"error: {str(e)[-200:]}"

    # fleet observability probe: two tenants with different SLOs/arrival
    # rates on a two-replica fleet with the serving.obs plane enabled —
    # per-tenant goodput + burn-rate alert counts (docs/observability.md
    # "Fleet observability")
    try:
        if on_tpu:
            dur_mt, plen_mt, glen_mt = 12.0, (64, 192), (16, 48)
            slos_mt = {"gold": 8000.0, "bronze": 50.0}
            rates_mt = {"gold": 8.0, "bronze": 16.0}
            slots_mt, bs_mt = 12, 32
        else:
            dur_mt, plen_mt, glen_mt = 3.0, (12, 24), (3, 8)
            # gold's SLO is generous (met), bronze's is unmeetable (every
            # completion misses) — the burn alert must single out bronze
            slos_mt = {"gold": 30000.0, "bronze": 1.0}
            rates_mt = {"gold": 6.0, "bronze": 10.0}
            slots_mt, bs_mt = 6, 16
        max_tok_mt = plen_mt[1] + glen_mt[1]

        def build_mt():
            nb = slots_mt * ((max_tok_mt + bs_mt - 1) // bs_mt + 3) + 8
            return build_engine_v2(
                llama, mcfg, llama.init(mcfg, jax.random.PRNGKey(0)),
                config={"dtype": "bfloat16",
                        "prefill_bucket": min(64, plen_mt[1]),
                        "prefix_cache": {"enabled": True},
                        "ragged": {"max_tracked_sequences": slots_mt,
                                   "max_ragged_batch_size": slots_mt,
                                   "memory_config_blocks": nb,
                                   "block_size": bs_mt}})

        RESULT["detail"]["multitenant"] = run_multitenant(
            build_mt, sp, mcfg.vocab_size, dur_mt, plen_mt, glen_mt,
            slos_mt, rates_mt)
    except Exception as e:
        RESULT["detail"]["multitenant"] = f"error: {str(e)[-200:]}"

    # head-of-line probe: long-prompt admission stall, split vs one-shot
    try:
        if on_tpu:
            batch_hl, short_hl, long_hl, chunk_hl = 8, 64, 1536, 256
        else:
            batch_hl, short_hl, long_hl, chunk_hl = 4, 16, 96, 32
        nblocks = (batch_hl + 1) * ((long_hl + 256) // 32 + 3) + 8

        def build(split):
            return build_engine_v2(
                llama, mcfg, llama.init(mcfg, jax.random.PRNGKey(0)),
                config={"dtype": "bfloat16", "prefill_bucket": chunk_hl,
                        "split_prefill_chunk": split,
                        "ragged": {"max_tracked_sequences": batch_hl + 1,
                                   "max_ragged_batch_size": batch_hl + 1,
                                   "memory_config_blocks": nblocks,
                                   "block_size": 32}})

        RESULT["detail"]["longprompt_headofline"] = run_longprompt_probe(
            build, sp, mcfg.vocab_size, rng, batch_hl, short_hl, long_hl,
            chunk_hl)
        sys.stderr.write(
            f"[serving] headofline: "
            f"{RESULT['detail']['longprompt_headofline']}\n")
    except Exception as e:
        RESULT["detail"]["longprompt_headofline"] = f"error: {str(e)[-200:]}"
    RESULT["detail"]["params_m"] = round(mcfg.num_params / 1e6, 1)
    return finalize(RESULT)


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # report in the JSON line, then fail
        RESULT["detail"]["error"] = str(e)[-2000:]
        finalize(RESULT, ok=False)
        raise
