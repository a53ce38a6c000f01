#!/usr/bin/env python3
"""What the Command A+ cell's comparison with its plain reference can see, at
the cell's widths, sizes and engine settings (ISSUE 42, Tentpole 6): LOGITS,
not tokens - the cell's OWN comparison (``reference/cohere2_moe.py`` ``held``
and ``disagreements``, which ``logits_and_margin`` holds every probe of a run
to) given the right reference and then each deliberately wrong one. One
process, several seeds (``chiprun -- python3 benchmark/tools/
cohere2_check.py ...``). For each seed and each of the cell's probes:

``held``      the program's ``apply_paged`` - the prompt in padded chunks of
              the cell's SplitFuse size, then its own greedy tokens one at a
              time, in the served precision over the cell's block geometry,
              the window kind's blocks given back by a ``StateManager`` on
              the way (``families/cohere2_moe.py`` ``Program``) - against
              the right reference's full forward AND each wrong variant's
              (``reference/cohere2_moe_variants.py``), at the prompt's last
              row and every decoded row: mean and largest absolute
              difference. ``why_not`` is what the cell's limit says of it:
              empty for the right form, and for a wrong one only where the
              probe cannot tell them apart (a prompt inside the window for
              the two window variants: the control).
``program``   the right reference against three other PROGRAMS: the right
              one with everything the manager gave back POISONED (must read
              what the plain one reads: it never touches it), one that gives
              each window block back ``--early`` blocks too soon, poisoned
              (must fail beyond the window), and one whose weights are
              rounded to ``BELOW`` (fp8, the nearest precision below the
              configuration's bf16: must fail).
``served``    the longest probe once more THROUGH ``ServingScheduler.tick``
              beside live sequences (the mixed program with live rows,
              launched ahead): each served token's gap under the top of the
              right reference's logits.

Exit code 1 where the right form (plain or poisoned) is beyond the limit on
any probe, or a wrong form is inside it on every probe of a seed.

    cohere2_check.py --workload W --seeds 11,12 [--gains 1.0,2.0]
        [--decode 8] [--probes 1024,12288] [--tag T] [--rehearse]

``--gains`` serves the model at other attention-score gains than the
family's (``families/cohere2_moe.py`` ``QK_GAIN``): how the gain was chosen.
Nothing is timed and no result line is printed; every line also goes to
``chiprun_out/<tag>/<workload>.jsonl``; a summary is the last line.
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from keye_check import greedy, served_beside_live  # noqa: E402

BELOW = "float8_e5m2"   # the weights' type in the precision control: the fp8
#                         whose RANGE holds unscaled weights of spread 1/64
#                         (e4m3's least normal number is 1/64: most flush)


def rounded_in_place(params, below: str):
    """``params`` with every floating leaf rounded to the type ``below``
    names and back, each leaf DONATED to its own rounding: what was handed in
    is gone."""
    import jax

    from benchmark.families import cohere2_moe as family

    one = jax.jit(lambda p: family.rounded(p, below), donate_argnums=0)
    return jax.tree.map(one, params)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--gains", default="")
    ap.add_argument("--probes", default="")
    ap.add_argument("--decode", type=int, default=8)
    ap.add_argument("--early", type=int, default=1)
    ap.add_argument("--tag", default="cohere2_check")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--no-variants", action="store_true")
    ap.add_argument("--no-served", action="store_true")
    args = ap.parse_args()

    import numpy as np

    from benchmark.harness import device as dev
    from benchmark.harness import manifest
    from benchmark.reference import cohere2_moe_variants as variants
    from benchmark.traffic_kinds import closed_loop
    from benchmark.traffic_kinds.common import Run

    cell = manifest.Cell(args.workload, rehearsal=args.rehearse)
    family, ref, model = cell.family, cell.reference, cell.model
    dev.compile_cache_dir()
    device = dev.check(cell.chips, args.rehearse)
    out_dir = os.path.join(ROOT, "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    vocab, window = model["vocab_size"], model["sliding_window"]
    nums = lambda text, kind: [kind(s) for s in text.split(",") if s]
    gains = nums(args.gains, float) or [family.QK_GAIN]
    probes = nums(args.probes, int) or [n for n, _ in cell.traffic["probes"]]
    names = () if args.no_variants else tuple(variants.NAMES)
    programs = {"poisoned": {"poison": True},
                "released_early": {"poison": True,
                                   "release_early": args.early}}
    must_pass = ("right", "poisoned")
    role = family.serve_role(model)
    limits = {k: v for k, v in role["held"].items() if k != "why"}
    summary, wrong = {}, []

    with open(os.path.join(out_dir, cell.name + ".jsonl"), "w") as f:
        def say(**line):
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")

        for gain in gains:
            family.QK_GAIN = gain
            into = summary.setdefault(str(gain), {
                "diffs": {}, "gaps": [], "served_gaps": []})
            for seed in nums(args.seeds, int):
                t0 = time.perf_counter()
                eng = closed_loop.build(Run(
                    cell=cell, seed=seed, seconds=0.0, trace=False,
                    out_dir=out_dir, t_process=t0, device=device))
                weights = family.Weights(eng.params, role)
                rng = np.random.default_rng([seed, 0x9B0BE])
                caught = dict.fromkeys(
                    [n for n in names + tuple(programs) + (BELOW,)
                     if n not in must_pass], False)
                kept = []       # (probe, tokens, the right reference's rows)
                for n in probes:
                    prompt = rng.integers(0, vocab, n).tolist()
                    out = greedy(eng, prompt, args.decode)
                    tokens = np.asarray(prompt + out, np.int32)
                    rows = args.decode + 1
                    got = weights.program.logits(model, tokens, args.decode)
                    right = ref.logits(model, weights, tokens, rows=rows)
                    kept.append((n, tokens, right))

                    def judge(name, got, want, kind, n=n):
                        seen = ref.held(got, want)
                        why = ref.disagreements(seen, limits)
                        into["diffs"].setdefault(name, []).append(
                            seen["logits_mean_abs_diff"])
                        say(part=kind, gain=gain, seed=seed, prompt=n,
                            name=name, **seen, why_not=why)
                        if name in must_pass and why:
                            wrong.append(f"seed {seed}, prompt {n}: the "
                                         f"{name} form: {why}")
                        elif name in caught:
                            caught[name] |= bool(why)

                    judge("right", got, right, "held")
                    gaps = right.max(-1) - right[np.arange(rows),
                                                 got.argmax(-1)]
                    into["gaps"] += gaps.tolist()
                    for name in names:
                        judge(name, got, variants.logits(
                            name, model, weights, tokens, rows=rows), "held")
                    for name, kw in programs.items():
                        other = family.Program(eng.params, role, **kw)
                        judge(name, other.logits(model, tokens, args.decode),
                              right, "program")
                        del other
                if not args.no_served:
                    prompt = rng.integers(0, vocab, max(probes)).tolist()
                    out, mixed, ahead = served_beside_live(
                        cell, eng, prompt, args.decode, seed)
                    tokens = np.asarray(prompt + out[:-1], np.int32)
                    want = ref.logits(model, weights, tokens, rows=len(out))
                    gaps = want.max(-1) - want[np.arange(len(out)), out]
                    into["served_gaps"] += gaps.tolist()
                    say(part="served", gain=gain, seed=seed,
                        prompt=len(prompt), mixed_steps=mixed,
                        overlapped_steps=ahead,
                        gaps=[round(float(g), 4) for g in gaps])
                # the precision control LAST: the weights are rounded where
                # they lie (a second copy does not fit beside the first), so
                # the engine that served them is gone by then
                params = eng.params
                del eng, weights
                gc.collect()
                params = rounded_in_place(params, BELOW)
                below = family.Program(params, role)
                for n, tokens, right in kept:
                    judge(BELOW, below.logits(model, tokens, args.decode),
                          right, "program", n)
                del below, params
                gc.collect()
                for name, hit in caught.items():
                    if not hit and max(probes) > window:
                        wrong.append(f"seed {seed}: {name} is inside the "
                                     f"limit on every probe")
                say(gain=gain, seed=seed, seconds=time.perf_counter() - t0)
        out = {"part": "summary", "workload": cell.name, "device": device,
               "limits": {**limits, "served_token_gap":
                          closed_loop.SERVED_TOKEN_GAP_TOL},
               "wrong": wrong, "gains": {}}
        for gain, s in summary.items():
            out["gains"][gain] = {
                "mean_abs_diff_largest": {
                    n: max(v) for n, v in s["diffs"].items()
                    if n in must_pass},
                "mean_abs_diff_least": {
                    n: min(v) for n, v in s["diffs"].items()
                    if n not in must_pass},
                "mean_abs_diff_at_longest_probe": {
                    n: v[-1] for n, v in s["diffs"].items()},
                "largest_gap": max(s["gaps"]),
                "largest_served_gap": max(s["served_gaps"], default=None)}
        say(**out)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
