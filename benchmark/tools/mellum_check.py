#!/usr/bin/env python3
"""What the Mellum 2 cell's comparison with its plain reference can see, at
the cell's widths, sizes and engine settings (ISSUE 61): LOGITS, not tokens -
the cell's OWN comparison (``reference/mellum.py`` ``held`` and
``disagreements``, which ``logits_and_margin`` holds every probe of a run to)
given the right reference and then each deliberately wrong one. One process,
several seeds (``chiprun -- python3 benchmark/tools/mellum_check.py ...``).
For each seed and each of the cell's probes:

``held``      the program's ``apply_paged`` AS THE WINDOW CALLS IT - every
              call the engine's mixed call over the role's 32 slots, other
              sequences live in the other slots, ONE table a slot serving
              both kinds (``families/mixed_program.py``): the probe's tokens
              (its prompt and the engine's own greedy answer) in padded
              chunks of the cell's SplitFuse size, then its last tokens one a
              tick, in the served precision - against the right reference's
              full forward AND each wrong variant's (``reference/
              mellum_variants.py``): the chunked part's last 64 rows and the
              decoded rows, the lower decile and the median of each under a
              limit of its own. ``why_not`` is what the cell's limits say of
              it: empty for the right form alone. A variant of the WINDOW
              (on the full layers, none, one token off) computes the right
              form on a probe that never leaves the window: it is judged on
              the longer probes.
``program``   the right reference against the program with its weights
              rounded to ``BELOW`` (fp8, the nearest precision below the
              configuration's bf16: must fail), and a fault that lives in the
              SINGLE-TOKEN segment alone, over the right program's prefilled
              pools: ``DECODE_NO_WINDOW``, the decode rows of a window layer
              reading their whole context - the chunked rows are the right
              program's own, so it must fail by the decoded rows' limits and
              by no other (on the probes longer than the window).
``released``  (``--released``) the longest probe through ``apply_paged`` over
              a ``StateManager``'s two-segment tables, the window kind's
              blocks GIVEN BACK on the way as the engine's are, against the
              same calls over one table that gives nothing back: program
              against program, the last chunk's rows and the decoded rows,
              held equal to the decoded median's limit (bf16's noise: the
              two walk the same keys in another tiling).
``served``    the longest probe once more THROUGH ``ServingScheduler.tick``
              beside live sequences (the mixed program with live rows,
              launched ahead, its window blocks given back): each served
              token's gap under the top of the right reference's logits.

``--gains 1.0,2.0`` repeats the lot with Wq and Wk drawn at another
``families/mellum.py QK_GAIN``, ``--router-gains`` with the routers' columns
at another ``ROUTER_GAIN`` (every pair of the two lists). Exit code 1 where the right form is beyond a
limit on any probe, a wrong form is inside every limit on a probe it is
judged on, or the released form differs.

    mellum_check.py --workload W --seeds 11,12 [--probes 512,3072]
        [--gains 1.0] [--router-gains 1.0] [--released] [--tag T]
        [--rehearse]

Nothing is timed and no result line is printed; every line also goes to
``chiprun_out/<tag>/<workload>.jsonl``; a summary is the last line.
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from cohere2_check import BELOW  # noqa: E402  (the fp8 whose range holds
#                                  unscaled weights: its docstring)
from keye_check import greedy, served_beside_live  # noqa: E402
from nemotron_h_check import rounded_in_place  # noqa: E402

DECODE_NO_WINDOW = "decode_no_window"   # planted in the single-token
#                                         segment alone
WINDOW_FORMS = ("window_on_full", "no_window", "window_1023", "window_1025",
                DECODE_NO_WINDOW)
SERVED_STEPS = 8


@contextlib.contextmanager
def decode_without_window():
    """While this is open, a program TRACED from ``models/_paged.py`` walks a
    single-token call's whole context on a window layer too - the fault of a
    decode kernel that drops the window the chunk kernel keeps. Every
    multi-token call is as it was."""
    from deepspeed_tpu.models import _paged

    real = _paged.paged_attention_step

    def step(q, *args, window=None, **kw):
        mixed = isinstance(args[4], _paged.MixedCall)
        if not mixed and q.shape[1] == 1:
            window = None
        return real(q, *args, window=window, **kw)

    _paged.paged_attention_step = step
    try:
        yield
    finally:
        _paged.paged_attention_step = real


def released_against_one_table(program, tokens, decode: int):
    """``(given back, one table)``: the logits of the last chunk's judged
    rows and of the ``decode`` single-token calls, ``apply_paged`` over ONE
    sequence - through a ``StateManager``'s two-segment tables with the
    window kind's pool as the engine sizes a slot's, and through one table
    of the full kind's width with a window pool as large."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.ragged import StateManager, WindowKind

    assert program.call is not None, "after the probe's own prefill"
    m, cfg = program.family.module(), program.cfg
    block, chunk, width = program.block, program.chunk, program.width
    rows = 64

    def call(params, cache, table, toks, ctx, n_valid):
        valid = jnp.arange(toks.shape[1])[None] < n_valid
        at = jnp.clip(n_valid - rows + jnp.arange(rows), 0, None)[None]
        logits, cache = m.apply_paged(cfg, params, toks, cache, table, ctx,
                                      valid=valid, rows=at,
                                      compute_dtype=program.dtype)
        return logits[0], cache

    call = jax.jit(call, donate_argnums=(1,))
    n = len(tokens) - decode
    calls = [(a, min(a + chunk, n), chunk) for a in range(0, n, chunk)] \
        + [(i, i + 1, 1) for i in range(n, len(tokens))]
    out = []
    for released in (True, False):
        kinds = tuple(
            WindowKind.sized(name, window, 1, chunk, block)
            for name, window in m.window_kinds(cfg).items()) \
            if released else ()
        state = StateManager(1, width + 1, block, width, window_kinds=kinds)
        cache = m.init_paged_cache(
            cfg, width + 1, block, dtype=program.dtype,
            **({"window_blocks": {k.name: k.num_blocks for k in kinds}}
               if released else {}))
        desc, got = state.admit(0, n), []
        for start, end, t in calls:
            padded = np.zeros((1, t), np.int32)
            padded[0, :end - start] = tokens[start:end]
            state.extend(desc, end - start)
            logits, cache = call(
                program.params, cache,
                jnp.asarray(state.block_table(desc)[None]),
                jnp.asarray(padded), jnp.asarray([start], jnp.int32),
                jnp.asarray(end - start, jnp.int32))
            desc.seen_tokens = end
            if t == 1:
                got.append(np.asarray(logits[-1:]))
            elif end == n:
                got.append(np.asarray(logits[-min(rows, end - start):]))
        if released:
            assert state.window_blocks_released > 0 \
                or len(tokens) <= min(m.window_kinds(cfg).values()) + block
            state.debug_check()
        del cache
        out.append(np.concatenate(got))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--probes", default="")
    ap.add_argument("--gains", default="")
    ap.add_argument("--router-gains", default="")
    ap.add_argument("--tag", default="mellum_check")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--released", action="store_true")
    ap.add_argument("--no-variants", action="store_true")
    ap.add_argument("--no-served", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.families import mixed_program
    from benchmark.harness import device as dev
    from benchmark.harness import manifest
    from benchmark.reference import mellum_variants as variants
    from benchmark.traffic_kinds import closed_loop
    from benchmark.traffic_kinds.common import Run

    cell = manifest.Cell(args.workload, rehearsal=args.rehearse)
    family, ref, model = cell.family, cell.reference, cell.model
    dev.compile_cache_dir()
    device = dev.check(cell.chips, args.rehearse)
    out_dir = os.path.join(ROOT, "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    vocab, window = model["vocab_size"], model["sliding_window"]
    nums = lambda text: [int(s) for s in text.split(",") if s]
    probes = nums(args.probes) or [n for n, _ in cell.traffic["probes"]]
    floats = lambda text, default: [float(g) for g in text.split(",")
                                    if g] or [default]
    # (a QK gain, a router gain): every pair of the two lists
    gains = [(q, r) for q in floats(args.gains, family.QK_GAIN)
             for r in floats(args.router_gains, family.ROUTER_GAIN)]
    names = () if args.no_variants else tuple(variants.NAMES)
    role = family.serve_role(model)
    limits = {k: v for k, v in role["held"].items() if k != "why"}
    diffs, served_gaps, gaps_all, wrong, decided = {}, [], [], [], {}
    faulty = None       # the single-token program with the fault planted

    with open(os.path.join(out_dir, cell.name + ".jsonl"), "w") as f:
        def say(**line):
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")

        for gain, seed in ((g, s) for g in gains for s in nums(args.seeds)):
            t0 = time.perf_counter()
            # (read when the weights are drawn)
            family.QK_GAIN, family.ROUTER_GAIN = gain
            eng = closed_loop.build(Run(
                cell=cell, seed=seed, seconds=0.0, trace=False,
                out_dir=out_dir, t_process=t0, device=device))
            weights = family.Weights(eng.params, role)
            program = weights.program
            rng = np.random.default_rng([seed, 0x501A])
            kept = []   # (probe, tokens, decode, the right reference's rows)

            def judge(name, got, want, kind, n, decode):
                seen = ref.held(got, want, decode)
                why = ref.disagreements(seen, limits)
                judged = name not in WINDOW_FORMS or n + SERVED_STEPS > window
                if judged:
                    for key, _, _ in ref.HELD:
                        diffs.setdefault((gain, key), {}).setdefault(
                            name, []).append(seen[key])
                say(part=kind, gain=gain, seed=seed, prompt=n, name=name,
                    judged=judged, **seen, why_not=why)
                where = f"gain {gain}, seed {seed}, prompt {n}: {name}"
                if name == "right":
                    wrong.extend([f"{where}: {why}"] if why else [])
                elif not judged:
                    pass
                elif name == DECODE_NO_WINDOW:
                    if not (why and all("decoded" in w for w in why)):
                        wrong.append(f"{where} is not told by the decoded "
                                     f"rows' limits alone: {why}")
                elif not why:
                    wrong.append(f"{where} is inside every limit")

            for n in probes:
                prompt = rng.integers(0, vocab, n).tolist()
                tokens = np.asarray(
                    prompt + greedy(eng, prompt, SERVED_STEPS), np.int32)
                decode = ref.decode_rows(len(tokens))
                cut = len(tokens) - decode
                pre, cache, book = program.prefill(model, tokens, cut)
                pools = jax.device_get(cache)
                got = np.concatenate(
                    [pre, program.decode(model, tokens, cut, cache, book)])
                rows = len(got)     # the chunked part's last rows, then the
                #                     decoded
                margins = []
                right = ref.logits(model, weights, tokens, rows=rows,
                                   margins=margins)
                kept.append((n, tokens, decode, right))
                judge("right", got, right, "held", n, decode)
                margin = np.asarray(ref.routing_margin(
                    margins, len(tokens))[-rows:])
                gaps = right.max(-1) - right[np.arange(rows),
                                             got.argmax(-1)]
                gaps_all += gaps.tolist()
                clear = margin > closed_loop.ROUTER_MARGIN_TOL
                decided.setdefault(gain, []).extend(clear.tolist())
                # each judged row's reading beside its routing margin (in
                # the harness's units) and the gap of the program's own top
                # under the reference's
                say(part="rows", gain=gain, seed=seed, prompt=n,
                    decode=decode,
                    rows=[round(float(r), 4) for r in
                          np.abs(got - right).mean(-1)],
                    margins=[round(float(m), 4) for m in margin],
                    gaps=[round(float(g), 4) for g in gaps],
                    largest_gap=float(gaps.max()),
                    largest_decided_gap=float(gaps[clear].max(initial=0)),
                    decided_beyond=int((gaps[clear]
                                        > closed_loop.SERVED_TOKEN_GAP_TOL
                                        ).sum()))
                # the fault planted in the single-token segment alone, over
                # the right program's pools (its jit is traced while the
                # plant is open: the first call)
                with decode_without_window():
                    faulty = faulty or mixed_program.mixed_call.__wrapped__(
                        family, program.cfg, program.dtype.name)
                    judge(DECODE_NO_WINDOW, np.concatenate(
                        [pre, program.decode(
                            model, tokens, cut, jax.device_put(pools), book,
                            call=faulty)]), right, "program", n, decode)
                del pools
                for name in names:
                    judge(name, got, variants.logits(
                        name, model, weights, tokens, rows=rows), "held", n,
                        decode)
            if args.released:
                n, tokens, decode, _ = kept[-1]
                given, one = released_against_one_table(program, tokens,
                                                        decode)
                seen = ref.held(given, one, decode)
                limit = limits["decode_median_row_mean_abs_diff"]
                keys = [k for k, _, _ in ref.HELD]
                say(part="released", gain=gain, seed=seed, prompt=n, **seen,
                    limit=limit)
                if not all(seen[k] <= limit for k in keys):
                    wrong.append(f"gain {gain}, seed {seed}, prompt {n}: "
                                 f"the program over given-back blocks is "
                                 f"not the program over one table: {seen}")
            if not args.no_served:
                prompt = rng.integers(0, vocab, max(probes)).tolist()
                out, mixed, ahead = served_beside_live(
                    cell, eng, prompt, SERVED_STEPS, seed)
                tokens = np.asarray(prompt + out[:-1], np.int32)
                want = ref.logits(model, weights, tokens, rows=len(out))
                gaps = want.max(-1) - want[np.arange(len(out)), out]
                served_gaps += gaps.tolist()
                say(part="served", gain=gain, seed=seed, prompt=len(prompt),
                    mixed_steps=mixed, overlapped_steps=ahead,
                    window_blocks_released=eng.state.window_blocks_released,
                    gaps=[round(float(g), 4) for g in gaps])
            # the precision control LAST: the weights are rounded where they
            # lie, so the engine that served them is gone by then
            params = eng.params
            del eng, weights, program
            gc.collect()
            below = family.Program(rounded_in_place(params, BELOW), role)
            del params
            for n, tokens, decode, right in kept:
                judge(BELOW, below.logits(model, tokens, decode), right,
                      "program", n, decode)
            del below, kept
            gc.collect()
            say(gain=gain, seed=seed, seconds=time.perf_counter() - t0)
        band = lambda gain, key: {
            n: [min(v), max(v)] for n, v in diffs[(gain, key)].items()}
        say(part="summary", workload=cell.name, device=device,
            limits={**limits,
                    "served_token_gap": closed_loop.SERVED_TOKEN_GAP_TOL},
            wrong=wrong,
            ranges={str(gain): {key: band(gain, key)
                                for key, _, _ in ref.HELD}
                    for gain in gains},
            decided_share={str(g): sum(v) / len(v)
                           for g, v in decided.items()},
            largest_gap=max(gaps_all),
            largest_served_gap=max(served_gaps, default=None))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
