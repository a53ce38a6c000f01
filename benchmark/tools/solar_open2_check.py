#!/usr/bin/env python3
"""What the Solar-Open2 cell's comparison with its plain reference can see,
at the cell's widths, sizes and engine settings (ISSUE 57): LOGITS, not
tokens - the cell's OWN comparison (``reference/solar_open2.py`` ``held`` and
``disagreements``, which ``logits_and_margin`` holds every probe of a run
to) given the right reference and then each deliberately wrong one. One
process, several seeds (``chiprun -- python3
benchmark/tools/solar_open2_check.py ...``). For each seed and each of the
cell's probes:

``held``      the program's ``apply_paged`` AS THE WINDOW CALLS IT - every
              call the engine's mixed call over the role's 16 slots, other
              sequences live in the other slots (``families/
              mixed_program.py``): the prompt in padded chunks of the cell's
              SplitFuse size through ``delta_chunk`` beside the neighbours'
              decode rows, then its own greedy tokens one a tick through
              ``delta_decode_update`` beside another sequence's chunk, in
              the served precision
              - against the right reference's full forward AND each wrong
              variant's (``reference/solar_open2_variants.py``): the chunked
              part's last 64 rows under one limit, the ``--decode`` decoded
              rows under another. ``why_not`` is what the cell's limits say
              of it: empty for the right form alone. A ``margins`` line a
              probe: each judged row's reading beside its routing margin.
``program``   the right reference against the program with its weights
              rounded to ``BELOW`` (fp8, the nearest precision below the
              configuration's bf16: must fail), and the fault that lives in
              the SINGLE-TOKEN call alone, over the right program's prefilled
              pools: ``DECODE_SCALAR_DECAY``, a state update that decays a
              head by the mean of its channels' decays - the chunked rows are
              the right program's own, so it must fail by the decoded rows'
              limits (its quiet row's and its median's) and by no other.
              ``STATE_BELOW``: the right program with its delta-rule
              state kept in bfloat16 (the configuration states float32; the
              Mosaic kernels refuse another type by name, so the two ops run
              their XLA forms) - REPORTED beside the limits, not required
              to fail: PERF.md section 6 says what it read.
``served``    the longest probe once more THROUGH ``ServingScheduler.tick``
              beside live sequences (the mixed program with live rows,
              launched ahead): each served token's gap under the top of the
              right reference's logits.

Exit code 1 where the right form is beyond a limit on any probe, or a wrong
form is inside both on any probe.

    solar_open2_check.py --workload W --seeds 11,12 [--decode 96]
        [--probes 1024,2048] [--tag T] [--rehearse]

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

DECODE_SCALAR_DECAY = "decode_scalar_decay"   # planted in the single-token
#                                               segment alone
STATE_BELOW = "bf16_state_program"   # the program's state in bfloat16
STATE_OPS = ("delta_decode_update", "delta_chunk")


@contextlib.contextmanager
def xla_state_ops():
    """While this is open, the two delta-rule ops resolve to their XLA forms
    (``ops/delta.py``), which take a state of any type."""
    from deepspeed_tpu.ops import registry

    for name in STATE_OPS:
        registry.set_backend(name, "xla")
    try:
        yield
    finally:
        for name in STATE_OPS:
            registry.set_backend(name, None)


@contextlib.contextmanager
def scalar_decay_update():
    """While this is open, a program TRACED from ``models/solar_open2.py``
    takes a single-token state update that decays each head by the MEAN of
    its channels' log-decays - the fault of a port that reads the decay as a
    gated delta rule's one scalar a head. The chunked form is as it was."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import solar_open2

    real = solar_open2.get_op

    def get_op(name):
        op = real(name)
        if name != "delta_decode_update":
            return op
        mean = lambda a: jnp.broadcast_to(a.mean(-1, keepdims=True), a.shape)
        return lambda *args, **kw: op(*args[:-2], mean(args[-2]), args[-1],
                                      **kw)

    solar_open2.get_op = get_op
    try:
        yield
    finally:
        solar_open2.get_op = real


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--probes", default="")
    ap.add_argument("--decode", type=int, default=0)
    ap.add_argument("--tag", default="solar_open2_check")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--no-variants", action="store_true")
    ap.add_argument("--no-served", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.families import mixed_program
    from benchmark.harness import device as dev
    from benchmark.harness import manifest
    from benchmark.reference import solar_open2_variants as variants
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
    diffs, gaps_all, served_gaps, wrong, decided = {}, [], [], [], []
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
            rng = np.random.default_rng([seed, 0x501A])
            kept = []   # (probe, tokens, decode, the right reference's rows)

            def judge(name, got, want, kind, n, decode):
                seen = ref.held(got, want, decode)
                why = ref.disagreements(seen, limits)
                for key, _, _ in ref.HELD:
                    diffs.setdefault(key, {}).setdefault(name, []).append(
                        seen[key])
                say(part=kind, seed=seed, prompt=n, name=name, **seen,
                    why_not=why)
                where = f"seed {seed}, prompt {n}: {name}"
                if name == "right":
                    wrong.extend([f"{where}: {why}"] if why else [])
                elif name == DECODE_SCALAR_DECAY:
                    if not (why and all("decoded" in w for w in why)):
                        wrong.append(f"{where} is not told by the decoded "
                                     f"rows' limits alone: {why}")
                elif name == STATE_BELOW:
                    pass        # reported, not required to fail
                elif not why:
                    wrong.append(f"{where} is inside both limits")

            for n in probes:
                decode = args.decode or ref.decode_rows(n)
                prompt = rng.integers(0, vocab, n).tolist()
                out = greedy(eng, prompt, decode)
                tokens = np.asarray(prompt + out, np.int32)
                pre, cache, book = program.prefill(model, tokens, n)
                pools = jax.device_get(cache)
                got = np.concatenate(
                    [pre, program.decode(model, tokens, n, cache, book)])
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
                decided += (margin > closed_loop.ROUTER_MARGIN_TOL).tolist()
                # each judged row's reading beside its routing margin (in
                # the harness's units)
                say(part="margins", seed=seed, prompt=n, decode=decode,
                    rows=[round(float(r), 4) for r in
                          np.abs(got - right).mean(-1)],
                    margins=[round(float(m), 4) for m in margin],
                    gaps=[round(float(g), 4) for g in gaps])
                gaps_all += gaps.tolist()
                # the fault planted in the single-token segment alone, over
                # the right program's pools (its jit is traced while the
                # plant is open: the first call)
                with scalar_decay_update():
                    faulty = faulty or mixed_program.mixed_call.__wrapped__(
                        family, program.cfg, program.dtype.name)
                    judge(DECODE_SCALAR_DECAY, np.concatenate(
                        [pre, program.decode(
                            model, tokens, n, jax.device_put(pools), book,
                            call=faulty)]), right, "program", n, decode)
                del pools
                # the state's type: the same weights, the state in bfloat16
                if not args.no_variants:
                    with xla_state_ops():
                        judge(STATE_BELOW, family.Program(
                            eng.params, role,
                            options={"state_dtype": "bfloat16"}).logits(
                                model, tokens, decode), right, "program", n,
                            decode)
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
            chunked_median_range=band("median_row_mean_abs_diff"),
            decoded_median_range=band("decode_median_row_mean_abs_diff"),
            decided_share=sum(decided) / max(len(decided), 1),
            largest_gap=max(gaps_all),
            largest_served_gap=max(served_gaps, default=None))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
