#!/usr/bin/env python3
"""What the ZAYA1 cell's comparison with its plain reference can see, at the
cell's widths, sizes and engine settings (ISSUE 64): LOGITS, not tokens - the
cell's OWN comparison (``reference/zaya.py`` ``held`` and ``disagreements``,
which ``logits_and_margin`` holds every probe of a run to) given the right
reference and then each deliberately wrong one. One process, several seeds
(``chiprun -- python3 benchmark/tools/zaya_check.py ...``). For each seed and
each of the cell's probes:

``held``      the program's ``apply_paged`` AS THE WINDOW CALLS IT - every
              call the engine's mixed call over the role's 64 slots, other
              sequences live in the other slots, a tail row a slot
              (``families/mixed_program.py``): the probe's tokens (its prompt
              and the engine's own greedy answer) in padded chunks of the
              cell's SplitFuse size, then its last tokens one a tick, in the
              served precision - against the right reference's full forward
              AND each wrong variant's (``reference/zaya_variants.py``): the
              chunked part's last 64 rows and the decoded rows, the lower
              decile and the median of each and the upper quartile of them
              all, each under a limit of its own.
              ``why_not`` is what the cell's limits say of it: empty for the
              right form alone. The two TAIL forms are a served program's
              faults (what a call's first row reads of the tokens before it):
              the reference computes them at the positions the program's
              calls began. ``tail_fp8`` is judged on the probes of at most
              one chunk (``SHORT_FORMS``) and ``router_bf16`` is reported,
              not required to fail (``REPORTED``), each for the reason
              beside its name.
``program``   the right reference against the program with its weights
              rounded to ``BELOW`` (fp8, the nearest precision below the
              configuration's bf16: must fail), and a fault that lives in the
              SINGLE-TOKEN segment alone, over the right program's prefilled
              pools: ``DECODE_NO_TAIL``, the decode rows mixing from zeros
              where their slot's tail belongs - the chunked rows are the
              right program's own, so it must fail by the decoded rows'
              limits and by no other.
``rows``      each judged row's reading beside its top-1 margin and the gap
              of the program's own top under the reference's; ``load``: the
              rows each of the 17 outputs was chosen by, a layer.
``served``    the longest probe once more THROUGH ``ServingScheduler.tick``
              beside live sequences (the mixed program with live rows,
              launched ahead): each served token's gap under the top of the
              right reference's logits.

``--router-gains 1.0,4.0`` repeats the lot with the router's last matrix at
another ``families/zaya.py ROUTER_GAIN``. Exit code 1 where the right form is
beyond a limit on any probe or a wrong form is inside every limit on a probe
it is judged on.

    zaya_check.py --workload W --seeds 11,12 [--probes 256,2048]
        [--router-gains 4.0] [--tag T] [--rehearse]

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

DECODE_NO_TAIL = "decode_no_tail"   # planted in the single-token segment alone
SERVED_STEPS = 8
# What bf16 serving cannot tell from the right form by limits that leave the
# right form room (the readings: the configuration's ``held``; PERF.md section
# 6, PR 64), REPORTED and not required to fail - the float32 CPU test holds
# each apart (``tests/test_zaya.py``): a router in bf16 adds rows whose top-1
# flipped; the quiet row and the median are blind to them BY DESIGN (the right
# form has such rows too: a float32 router on bf16 rows) and the upper
# quartile, which is not, read 1.0-1.5 x the right form's on the same probes
# and no more than the right form's own on another seed.
REPORTED = ("router_bf16",)
# ... and what is told only where a row's own q, k and v carry weight: a
# tail rounded to fp8 moves a call's first row through its own two keys, and
# over a context of thousands of keys that is bf16's noise. Judged on the
# probes of at most one chunk.
SHORT_FORMS = ("tail_fp8",)


@contextlib.contextmanager
def decode_without_tail():
    """While this is open, a program TRACED from ``models/zaya.py`` mixes a
    single-token segment's rows from zeros where their slots' tails belong -
    the fault of a decode step that does not read the pool. Every
    multi-token segment is as it was."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import zaya

    real = zaya.cca_mix

    def mix(cfg, w, p, v_now, v_shift, tail, fresh, positions, table):
        if p.shape[1] == 1:
            fresh = jnp.ones_like(fresh)
        return real(cfg, w, p, v_now, v_shift, tail, fresh, positions, table)

    zaya.cca_mix = mix
    try:
        yield
    finally:
        zaya.cca_mix = real


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--probes", default="")
    ap.add_argument("--router-gains", default="")
    ap.add_argument("--tag", default="zaya_check")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--no-variants", action="store_true")
    ap.add_argument("--no-served", action="store_true")
    ap.add_argument("--no-below", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.families import mixed_program
    from benchmark.harness import device as dev
    from benchmark.harness import manifest
    from benchmark.reference import zaya_variants as variants
    from benchmark.traffic_kinds import closed_loop
    from benchmark.traffic_kinds.common import Run

    cell = manifest.Cell(args.workload, rehearsal=args.rehearse)
    family, ref, model = cell.family, cell.reference, cell.model
    dev.compile_cache_dir()
    device = dev.check(cell.chips, args.rehearse)
    out_dir = os.path.join(ROOT, "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    vocab = model["vocab_size"]
    nums = lambda text: [int(s) for s in text.split(",") if s]
    probes = nums(args.probes) or [n for n, _ in cell.traffic["probes"]]
    gains = [float(g) for g in args.router_gains.split(",") if g] \
        or [family.ROUTER_GAIN]
    names = () if args.no_variants else tuple(variants.NAMES)
    role = family.serve_role(model)
    limits = {k: v for k, v in role["held"].items() if k != "why"}
    chunk = role["engine"]["split_prefill_chunk"]
    diffs, served_gaps, gaps_all, wrong, decided = {}, [], [], [], {}
    faulty = None       # the single-token program with the fault planted

    with open(os.path.join(out_dir, cell.name + ".jsonl"), "w") as f:
        def say(**line):
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")

        for gain, seed in ((g, s) for g in gains for s in nums(args.seeds)):
            t0 = time.perf_counter()
            family.ROUTER_GAIN = gain       # (read when the weights are drawn)
            eng = closed_loop.build(Run(
                cell=cell, seed=seed, seconds=0.0, trace=False,
                out_dir=out_dir, t_process=t0, device=device))
            weights = family.Weights(eng.params, role)
            program = weights.program
            rng = np.random.default_rng([seed, 0x2A7A])
            kept = []   # (probe, tokens, decode, the right reference's rows)

            def judge(name, got, want, kind, n, decode):
                seen = ref.held(got, want, decode)
                why = ref.disagreements(seen, limits)
                judged = name not in REPORTED and (
                    name not in SHORT_FORMS or n <= chunk)
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
                elif name == DECODE_NO_TAIL:
                    # (the upper quartile is of EVERY judged row, the
                    # decoded among them)
                    if not (any("decoded" in w for w in why)
                            and not any("chunked" in w for w in why)):
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
                starts = ref.call_starts(len(tokens), decode, chunk)
                pre, cache, book = program.prefill(model, tokens, cut)
                pools = jax.device_get(cache)
                got = np.concatenate(
                    [pre, program.decode(model, tokens, cut, cache, book)])
                rows = len(got)     # the chunked part's last rows, then the
                #                     decoded
                margins, counts = [], []
                right = ref.logits(model, weights, tokens, rows=rows,
                                   margins=margins, counts=counts)
                kept.append((n, tokens, decode, right))
                judge("right", got, right, "held", n, decode)
                margin = np.asarray(ref.routing_margin(
                    margins, len(tokens))[-rows:])
                gaps = right.max(-1) - right[np.arange(rows),
                                             got.argmax(-1)]
                gaps_all += gaps.tolist()
                clear = margin > closed_loop.ROUTER_MARGIN_TOL
                decided.setdefault(gain, []).extend(clear.tolist())
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
                say(part="load", gain=gain, seed=seed, prompt=n,
                    tokens=len(tokens),
                    skipped_share=float(sum(int(c[-1]) for c in counts)
                                        / (len(counts) * len(tokens))),
                    by_output=[c.tolist() for c in counts])
                # the fault planted in the single-token segment alone, over
                # the right program's pools (its jit is traced while the
                # plant is open: the first call)
                with decode_without_tail():
                    faulty = faulty or mixed_program.mixed_call.__wrapped__(
                        family, program.cfg, program.dtype.name)
                    judge(DECODE_NO_TAIL, np.concatenate(
                        [pre, program.decode(
                            model, tokens, cut, jax.device_put(pools), book,
                            call=faulty)]), right, "program", n, decode)
                del pools
                for name in names:
                    judge(name, got, variants.logits(
                        name, model, weights, tokens, starts=starts,
                        rows=rows), "held", n, decode)
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
                    gaps=[round(float(g), 4) for g in gaps])
            # the precision control LAST: the weights are rounded where they
            # lie, so the engine that served them is gone by then
            params = eng.params
            del eng, weights, program
            gc.collect()
            if not args.no_below:
                below = family.Program(rounded_in_place(params, BELOW), role)
                del params
                for n, tokens, decode, right in kept:
                    judge(BELOW, below.logits(model, tokens, decode), right,
                          "program", n, decode)
                del below
            del kept
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
