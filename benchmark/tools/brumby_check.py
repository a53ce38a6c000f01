#!/usr/bin/env python3
"""What the Brumby cell's comparison with its plain reference can see, at
the cell's widths, sizes and engine settings (ISSUE 55): LOGITS, not tokens -
the cell's OWN comparison (``reference/brumby.py`` ``held`` and
``disagreements``, which ``logits_and_margin`` holds every probe of a run
to) given the right reference and then each deliberately wrong one. One
process, several seeds (``chiprun -- python3 benchmark/tools/brumby_check.py
...``). For each seed and each of the cell's probes:

``held``      the program's ``apply_paged`` - the prompt in padded chunks of
              the cell's SplitFuse size through ``retention_chunk``, then its
              own greedy tokens one at a time through
              ``retention_decode_update``, in the served precision
              (``families/brumby.py`` ``Program``) - against the right
              reference's full forward AND each wrong variant's
              (``reference/brumby_variants.py``): the chunked part's last 64
              rows under one limit, the ``--decode`` decoded rows under
              another. ``why_not`` is what the cell's limits say of it: empty
              for the right form alone.
``program``   the right reference against the program with its weights
              rounded to ``BELOW`` (fp8, the nearest precision below the
              configuration's bf16: must fail). (The STATE's type is held by
              the reference's ``bf16_state`` variant, reported, and by the
              float32 CPU test: the Mosaic kernels stream a float32 state
              and refuse another by name.) And the
              fault that lives in the SINGLE-TOKEN call alone, over the right
              program's prefilled pool: ``DECODE_ONE_GATE``, a state update
              that takes key-value head 0's gate for every head - the chunked
              rows are the right program's own, so it must fail by the
              decoded rows' limit and by no other.
``served``    the longest probe once more THROUGH ``ServingScheduler.tick``
              beside live sequences (the mixed program with live rows,
              launched ahead): each served token's gap under the top of the
              right reference's logits.

Exit code 1 where the right form is beyond a limit on any probe, or a wrong
form (but the ``REPORTED`` ones) is inside both on any probe.

    brumby_check.py --workload W --seeds 11,12 [--decode 96]
        [--probes 256,2048] [--tag T] [--rehearse]

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

DECODE_ONE_GATE = "decode_one_gate"  # planted in the single-token call alone
# variants whose reading is reported and not required to fail: a state kept
# in bfloat16, in the reference as in the program (the float32 CPU test holds
# the state's type: tests/test_brumby.py; the configuration's ``held.why``
# says what the chip read)
REPORTED = ("bf16_state",)


@contextlib.contextmanager
def one_gate_update():
    """While this is open, a program TRACED from ``models/brumby.py`` takes a
    single-token state update that reads key-value head 0's gate for every
    head - the fault of a port that gates the state by one scalar a token.
    The chunked form is as it was."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import brumby

    real = brumby.get_op

    def get_op(name):
        op = real(name)
        if name != "retention_decode_update":
            return op
        first = lambda a: jnp.broadcast_to(a[:, :1], a.shape)
        return lambda *args, **kw: op(*args[:-1], first(args[-1]), **kw)

    brumby.get_op = get_op
    try:
        yield
    finally:
        brumby.get_op = real


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--probes", default="")
    ap.add_argument("--decode", type=int, default=0)
    ap.add_argument("--tag", default="brumby_check")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--no-variants", action="store_true")
    ap.add_argument("--no-served", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.harness import device as dev
    from benchmark.harness import manifest
    from benchmark.reference import brumby_variants as variants
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
    faulty = None       # the single-token program with the fault planted

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
            program = weights.program
            rng = np.random.default_rng([seed, 0xB2B1])
            kept = []   # (probe, tokens, decode, the right reference's rows)

            def judge(name, got, want, kind, n, decode):
                seen = ref.held(got, want, decode)
                why = ref.disagreements(seen, limits)
                for key in ("logits_mean_abs_diff",
                            "decode_logits_mean_abs_diff"):
                    diffs.setdefault(key, {}).setdefault(name, []).append(
                        seen[key])
                say(part=kind, seed=seed, prompt=n, name=name, **seen,
                    why_not=why)
                where = f"seed {seed}, prompt {n}: {name}"
                if name == "right":
                    wrong.extend([f"{where}: {why}"] if why else [])
                elif name == DECODE_ONE_GATE:
                    if not (len(why) == 1 and "decoded" in why[0]):
                        wrong.append(f"{where} is not told by the decoded "
                                     f"rows' limit alone: {why}")
                elif name not in REPORTED and not why:
                    wrong.append(f"{where} is inside both limits")

            for n in probes:
                decode = args.decode or ref.decode_rows(n)
                prompt = rng.integers(0, vocab, n).tolist()
                out = greedy(eng, prompt, decode)
                tokens = np.asarray(prompt + out, np.int32)
                pre, cache = program.prefill(model, tokens, n)
                pool = jax.device_get(cache)
                got = np.concatenate(
                    [pre, program.decode(model, tokens, n, cache)])
                rows = len(got)     # the chunked part's last rows, then the
                #                     decoded
                right = ref.logits(model, weights, tokens, rows=rows)
                kept.append((n, tokens, decode, right))
                judge("right", got, right, "held", n, decode)
                gaps_all += (right.max(-1) - right[
                    np.arange(rows), got.argmax(-1)]).tolist()
                # the fault planted in the single-token call alone, over the
                # right program's pool (its jit is traced while the plant is
                # open: the first call)
                with one_gate_update():
                    faulty = faulty or family.paged_call.__wrapped__(
                        program.cfg, program.dtype.name)
                    judge(DECODE_ONE_GATE, np.concatenate([pre, program.decode(
                        model, tokens, n, jax.device_put(pool),
                        call=faulty)]), right, "program", n, decode)
                del pool
                for name in names:
                    judge(name, got, variants.logits(
                        name, model, weights, tokens, rows=rows), "held", n,
                        decode)
            if not args.no_served:
                prompt = rng.integers(0, vocab, max(probes)).tolist()
                out, mixed, ahead = served_beside_live(
                    cell, eng, prompt, 8, seed)
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
            del eng, weights, program
            gc.collect()
            below = family.Program(rounded_in_place(params, BELOW), role)
            del params
            for n, tokens, decode, right in kept:
                judge(BELOW, below.logits(model, tokens, decode), right,
                      "program", n, decode)
            del below, kept
            gc.collect()
            say(seed=seed, seconds=time.perf_counter() - t0)
        band = lambda key: {
            n: [min(v), max(v)] for n, v in diffs[key].items()}
        say(part="summary", workload=cell.name, device=device,
            limits={**limits,
                    "served_token_gap": closed_loop.SERVED_TOKEN_GAP_TOL},
            wrong=wrong,
            chunked_rows_range=band("logits_mean_abs_diff"),
            decoded_rows_range=band("decode_logits_mean_abs_diff"),
            largest_gap=max(gaps_all),
            largest_served_gap=max(served_gaps, default=None))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
