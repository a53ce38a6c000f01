"""Traffic kind ``train``: optimizer steps on packed random tokens.

The cell's traffic file gives the batch per chip, the sequence length, how
many distinct batches the seed makes, the warm-up in steps and how many
steps a traced run profiles. The job runs through ``deepspeed_tpu.initialize``
and ``engine.train_batch``, the entry points a user calls.

Measurement (ISSUE 23): warm-up is counted in steps; one step stays in
flight (step k+1 is dispatched before the host blocks on step k's loss); a
step completes when that block returns; the window opens at a step boundary
and closes at the first boundary at least ``--seconds`` later; the rate is
the tokens of the window's whole steps, per chip, over the time between the
two boundaries - all the work over all the time, so a stall inside the
window shows. (The median step is printed on an earlier line: twelve runs
on the chip showed the window rate repeating to 0.0005 %, tighter than the
rate of the median step, so nothing is gained by a statistic that a stall
cannot move.)
"""

from __future__ import annotations

import math
import statistics
import time

import numpy as np

from ..harness import stats
from ..harness.device import jax_key
from .common import Record, Run

# The engine computes in bf16 with float32 accumulation and takes the loss in
# float32; the reference is float32 throughout. Rounding moves single logits
# by about 1% of their unit spread, with either sign, and the loss is a mean
# over thousands of tokens, so the two means agree closely: 2e-5 to 2e-4 on
# the chip at 4 x 2048 tokens (PR 23). Running the forward pass in a lower
# precision than bf16, a wrong mask or a wrong rotary phase moves it by far
# more than the tolerance.
REFERENCE_LOSS_TOL = 0.005
# Random-init logits have unit variance (unit-RMS final norm times a
# 1/sqrt(h) head), so the expected first loss is ln(vocab) + 1/2.
FIRST_LOSS_BAND = 0.25


def run(r: Run) -> Record:
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib

    cell, t, spans = r.cell, r.cell.traffic, r.spans
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    module = cell.family.module()
    batch = t["batch_per_chip"] * cell.chips
    seq = t["seq_len"]
    tokens_per_step = batch * seq
    why = []

    with spans.span("build"):
        mesh_lib.set_mesh(None)
        engine, _, _, _ = dst.initialize(
            model=module.model_spec(
                cfg, compute_dtype=jnp.dtype(cell.role["compute_dtype"])),
            config={**cell.role["engine"], "train_batch_size": batch},
            rng=jax_key(r.seed))
    rng = np.random.default_rng(r.seed)
    pool = [rng.integers(0, cell.model["vocab_size"], (batch, seq + 1),
                         dtype=np.int32) for _ in range(t["distinct_batches"])]

    # -- correct, part 1: the plain reference on the first batch, from the
    #    initial parameters (the step donates them) ----------------------- #
    with spans.span("reference"):
        rows = pool[0][:t["reference_sequences"]]
        ref_loss = float(cell.reference.loss(
            cell.model, cell.family.Weights(engine.state.params), rows))

    steps = 0

    def step():
        nonlocal steps
        with spans.span("next_batch"):
            b = {"tokens": pool[steps % len(pool)]}
        steps += 1
        with spans.span("train_batch"):
            return engine.train_batch(b)

    def wait(out) -> float:
        with spans.span("wait_step"):
            jax.block_until_ready(out.loss)
        return time.perf_counter()

    # first step: compiles (or loads from the cache)
    first = step()
    wait(first)
    first_loss = float(first.loss)
    losses = [first_loss]
    compile_stats = engine.telemetry.compile.summary
    for _ in range(t["warmup_steps"]):
        out = step()
        wait(out)
        losses.append(float(out.loss))
    compiles_before = int(compile_stats()["train_step"]["compiles"])

    outs = []   # every measured step's output; losses are read at the end

    def measure(until) -> list:
        """Completion times of consecutive steps, one step in flight, from
        an opening boundary until ``until(times)``."""
        times = []
        prev = step()
        while True:
            cur = step()
            times.append(wait(prev))
            outs.append(prev)
            prev = cur
            if until(times):
                break
        wait(prev)   # drain: the last dispatched step is not counted
        outs.append(prev)
        return times

    trace_dir = None
    if r.trace:
        with r.traced_window() as trace_dir:
            measure(lambda ts: len(ts) > t["trace_units"])
    times = measure(lambda ts: len(ts) > 1
                    and ts[-1] - ts[0] >= r.seconds)
    t_open = times[0]
    losses.extend(float(o.loss) for o in outs)
    compiles_in_window = int(compile_stats()["train_step"]["compiles"]) \
        - compiles_before
    start, end = stats.window_bounds(times, 0, r.seconds)
    steps_in_window = end - start
    gaps = stats.intervals(times, start, end)
    rate = stats.window_rate(
        times, [tokens_per_step / cell.chips] * len(times), start, end)

    # -- correct, part 2 --------------------------------------------------- #
    expected = math.log(cell.model["vocab_size"]) + 0.5
    bad = [x for x in losses if not math.isfinite(x)]
    if bad:
        why.append(f"{len(bad)} non-finite losses")
    if abs(first_loss - expected) >= FIRST_LOSS_BAND:
        why.append(f"first loss {first_loss:.4f} not within {FIRST_LOSS_BAND}"
                   f" of ln(vocab) + 1/2 = {expected:.4f}")
    ref_diff = None
    if len(rows) == batch:
        ref_diff = abs(first_loss - ref_loss)
        if not ref_diff <= REFERENCE_LOSS_TOL:
            why.append(f"first loss {first_loss:.5f} vs the plain "
                       f"reference's {ref_loss:.5f}: more than "
                       f"{REFERENCE_LOSS_TOL} apart")
    elif abs(ref_loss - expected) >= FIRST_LOSS_BAND:
        why.append(f"reference loss {ref_loss:.4f} outside the band")
    if compiles_in_window:
        why.append(f"{compiles_in_window} compilations inside the window")

    longest = max(range(len(gaps)), key=gaps.__getitem__)
    r.say(phase="train", steps_in_window=steps_in_window,
          window_s=times[end] - times[start],
          train_tokens_per_s_chip=rate,
          median_step_s=statistics.median(gaps), longest_step_s=gaps[longest],
          longest_step_index=longest, first_loss=first_loss,
          reference_loss=ref_loss, reference_diff=ref_diff,
          reference_sequences=len(rows), last_loss=losses[-1],
          compiles_in_window=compiles_in_window)
    series = {"kind": "train", "step_completion_s": times,
              "tokens_per_step": tokens_per_step, "chips": cell.chips,
              "losses": losses,
              "spans": [s for s in spans.records if s[2] >= t_open]}
    r.write_series(series)
    record = Record(
        correct=not why, attempted=steps, failed=len(bad),
        end_to_end={"train_tokens_per_s_chip": rate,
                    "setup_s": t_open - r.t_process},
        context={"series": series, "window": (start, end),
                 "tokens_per_step": tokens_per_step, "seq_len": seq,
                 "rate": rate,
                 "compiles_in_window": compiles_in_window},
        trace_dir=trace_dir, why_not_correct=why)
    return record
