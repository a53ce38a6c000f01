#!/usr/bin/env python3
"""What the A.X-K1 cell's comparison with its plain reference can see, at the
cell's widths, sizes and engine settings (ISSUE 47): LOGITS, not tokens - the
cell's OWN comparison (``reference/axk1.py`` ``held`` and ``disagreements``,
which ``logits_and_margin`` holds every probe of a run to) given the right
reference and then each deliberately wrong one. One process, several seeds
(``chiprun -- python3 benchmark/tools/axk1_check.py ...``). For each seed and
each of the cell's probes:

``held``      the program's ``apply_paged`` - the prompt in padded chunks of
              the cell's SplitFuse size, then its own greedy tokens one at a
              time, in the served precision: prefill then decode through the
              latent pool (``families/axk1.py`` ``Program``) - against the
              right reference's full forward AND each wrong variant's
              (``reference/axk1_variants.py``), at the prompt's last 64 rows
              and every decoded row. ``why_not`` is what the cell's limit says
              of it: empty for the right form alone.
``program``   the right reference against the program with its weights
              rounded to ``BELOW`` (fp8, the nearest precision below the
              configuration's bf16: must fail).
``served``    the longest probe once more THROUGH ``ServingScheduler.tick``
              beside live sequences (the mixed program with live rows,
              launched ahead): each served token's gap under the top of the
              right reference's logits.

Exit code 1 where the right form is beyond the limit on any probe, or a
wrong form is inside it on every probe of a seed.

    axk1_check.py --workload W --seeds 11,12 [--decode 8]
        [--probes 1024,12288] [--tag T] [--rehearse]

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

from cohere2_check import BELOW  # noqa: E402  (the fp8 whose range holds
#                                  unscaled weights: its docstring)
from keye_check import greedy, served_beside_live  # noqa: E402


def rounded_in_place(params, below: str):
    """``params`` with every floating leaf rounded to the type ``below``
    names and back, each leaf DONATED to its own rounding (a second copy of
    11 GB does not fit beside the first)."""
    import jax

    from benchmark.families import axk1 as family

    one = jax.jit(lambda p: family.rounded(p, below), donate_argnums=0)
    return jax.tree.map(one, params)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--probes", default="")
    ap.add_argument("--decode", type=int, default=8)
    ap.add_argument("--tag", default="axk1_check")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--no-variants", action="store_true")
    ap.add_argument("--no-served", action="store_true")
    args = ap.parse_args()

    import numpy as np

    from benchmark.harness import device as dev
    from benchmark.harness import manifest
    from benchmark.reference import axk1_variants as variants
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
    names = () if args.no_variants else tuple(variants.NAMES)
    role = family.serve_role(model)
    limits = {k: v for k, v in role["held"].items() if k != "why"}
    diffs, gaps_all, served_gaps, wrong = {}, [], [], []

    with open(os.path.join(out_dir, cell.name + ".jsonl"), "w") as f:
        def say(**line):
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")

        for seed in nums(args.seeds):
            t0 = time.perf_counter()
            eng = closed_loop.build(Run(
                cell=cell, seed=seed, seconds=0.0, trace=False,
                out_dir=out_dir, t_process=t0, device=device))
            weights = family.Weights(eng.params, role)
            rng = np.random.default_rng([seed, 0xA1C1])
            caught = dict.fromkeys(names + (BELOW,), False)
            kept = []       # (probe, tokens, the right reference's rows)

            def judge(name, got, want, kind, n):
                seen = ref.held(got, want)
                why = ref.disagreements(seen, limits)
                diffs.setdefault(name, []).append(
                    seen["logits_mean_abs_diff"])
                say(part=kind, seed=seed, prompt=n, name=name, **seen,
                    why_not=why)
                if name == "right" and why:
                    wrong.append(f"seed {seed}, prompt {n}: the right "
                                 f"form: {why}")
                elif name in caught:
                    caught[name] |= bool(why)

            for n in probes:
                prompt = rng.integers(0, vocab, n).tolist()
                out = greedy(eng, prompt, args.decode)
                tokens = np.asarray(prompt + out, np.int32)
                got = weights.program.logits(model, tokens, args.decode)
                rows = len(got)     # the prompt's last rows, then the decoded
                margins = []
                right = ref.logits(model, weights, tokens, rows=rows,
                                   margins=margins)
                kept.append((n, tokens, right))
                judge("right", got, right, "held", n)
                # each judged row's reading beside its routing margin (in
                # router logits): what MARGIN_SCALE was set from
                say(part="margins", seed=seed, prompt=n,
                    rows=[round(float(r), 4) for r in
                          np.abs(got - right).mean(-1)],
                    margins=[round(float(m), 4) for m in ref.routing_margin(
                        margins, len(tokens))[-rows:] / ref.MARGIN_SCALE])
                gaps_all += (right.max(-1) - right[
                    np.arange(rows), got.argmax(-1)]).tolist()
                for name in names:
                    judge(name, got, variants.logits(
                        name, model, weights, tokens, rows=rows), "held", n)
            if not args.no_served:
                prompt = rng.integers(0, vocab, max(probes)).tolist()
                out, mixed, ahead = served_beside_live(
                    cell, eng, prompt, args.decode, seed)
                tokens = np.asarray(prompt + out[:-1], np.int32)
                want = ref.logits(model, weights, tokens, rows=len(out))
                gaps = want.max(-1) - want[np.arange(len(out)), out]
                served_gaps += gaps.tolist()
                say(part="served", seed=seed, prompt=len(prompt),
                    mixed_steps=mixed, overlapped_steps=ahead,
                    gaps=[round(float(g), 4) for g in gaps])
            # the precision control LAST: the weights are rounded where they
            # lie, so the engine that served them is gone by then
            params = eng.params
            del eng, weights
            gc.collect()
            params = rounded_in_place(params, BELOW)
            below = family.Program(params, role)
            for n, tokens, right in kept:
                judge(BELOW, below.logits(model, tokens, args.decode), right,
                      "program", n)
            del below, params
            gc.collect()
            wrong += [f"seed {seed}: {name} is inside the limit on every "
                      f"probe" for name, hit in caught.items() if not hit]
            say(seed=seed, seconds=time.perf_counter() - t0)
        say(part="summary", workload=cell.name, device=device,
            limits={**limits,
                    "served_token_gap": closed_loop.SERVED_TOKEN_GAP_TOL},
            wrong=wrong,
            mean_abs_diff_largest={"right": max(diffs["right"])},
            mean_abs_diff_least={n: min(v) for n, v in diffs.items()
                                 if n != "right"},
            mean_abs_diff_at_longest_probe={n: v[-1]
                                            for n, v in diffs.items()},
            largest_gap=max(gaps_all),
            largest_served_gap=max(served_gaps, default=None))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
