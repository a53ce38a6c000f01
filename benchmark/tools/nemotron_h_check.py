#!/usr/bin/env python3
"""What the Nemotron-3-Nano cell's comparison with its plain reference can
see, at the cell's widths, sizes and engine settings (ISSUE 50): LOGITS, not
tokens - the cell's OWN comparison (``reference/nemotron_h.py`` ``held`` and
``disagreements``, which ``logits_and_margin`` holds every probe of a run
to) given the right reference and then each deliberately wrong one. One
process, several seeds (``chiprun -- python3 benchmark/tools/
nemotron_h_check.py ...``). For each seed and each of the cell's probes:

``held``      the program's ``apply_paged`` - the prompt in padded chunks of
              the cell's SplitFuse size, then its own greedy tokens one at a
              time, in the served precision: prefill through the chunked
              scan, then decode through the state update
              (``families/nemotron_h.py`` ``Program``) - against the
              right reference's full forward AND each wrong variant's
              (``reference/nemotron_h_variants.py``): the chunked part's last
              64 rows under one limit, the ``--decode`` decoded rows under
              another. ``why_not`` is what the cell's limits say of it: empty
              for the right form alone.
``program``   the right reference against the program with its weights
              rounded to ``BELOW`` (fp8, the nearest precision below the
              configuration's bf16: must fail), and against the program with
              its recurrent state kept in bfloat16 (``STATE_BELOW``: the
              reading is reported; whether it fails at these widths is what
              the configuration's ``held.why`` says). And the two faults
              that live in the SINGLE-TOKEN call alone, each over the right
              program's prefilled pools: ``DECODE_ONE_GROUP``, a state
              update that reads group 0's B and C for every head, and
              ``DECODE_BELOW``, the decode calls with fp8 weights - the
              chunked rows are the right program's own, so each must fail by
              the decoded rows' limit and by no other.
``load``      of the right reference's routing over the probe's rows: the
              share of rows that chose each held expert, layer by layer,
              beside the uniform router's ``top_k / experts`` that
              ``moe_rows_routed`` and ``costs_nemotron_h.
              held_experts_reached`` assume, and what a 64-row call would
              route and reach by these shares.
``served``    the longest probe once more THROUGH ``ServingScheduler.tick``
              beside live sequences (the mixed program with live rows,
              launched ahead): each served token's gap under the top of the
              right reference's logits.

Exit code 1 where the right form is beyond the limit on any probe, or a
wrong form is inside it on every probe of a seed.

    nemotron_h_check.py --workload W --seeds 11,12 [--decode 96]
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

STATE_BELOW = "bf16_state_program"   # the program's state in bfloat16
DECODE_ONE_GROUP = "decode_one_bc_group"     # planted in the decode alone
DECODE_BELOW = "decode_" + BELOW
# variants whose reading is reported and not required to fail: a state kept
# in bfloat16, in the reference as in the program, moves bf16-served logits
# by less than bf16 itself does (the float32 CPU test holds the state's type:
# tests/test_nemotron_h.py)
REPORTED = ("bf16_state",)


def rounded_in_place(params, below: str):
    """``params`` with every floating leaf rounded to the type ``below``
    names and back, each leaf DONATED to its own rounding (a second copy of
    8 GB does not fit beside the first and the pools)."""
    import jax

    from benchmark.families import nemotron_h as family

    one = jax.jit(lambda p: family.rounded(p, below), donate_argnums=0)
    return jax.tree.map(one, params)


@contextlib.contextmanager
def one_group_update():
    """While this is open, a program TRACED from ``models/granite_hybrid.py``
    ``_ssm_rows`` (this family's mixer too) takes a single-token state
    update that reads group 0's B and C for every head - the fault of a
    port that updates the state as Granite's one group does. The chunked
    scan is as it was."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import granite_hybrid

    real = granite_hybrid.get_op

    def get_op(name):
        op = real(name)
        if name != "ssm_decode_update":
            return op
        first = lambda a: jnp.broadcast_to(a[:, :1], a.shape)
        return lambda *args: op(*args[:-2], first(args[-2]), first(args[-1]))

    granite_hybrid.get_op = get_op
    try:
        yield
    finally:
        granite_hybrid.get_op = real


def load_line(model: dict, loads, rows: int = 64) -> dict:
    """What the right reference's routing over a probe's rows says of the
    uniform router that the bank's roofline counts by: ``loads`` a sparse
    layer's ``[seq, held]`` choices each."""
    import numpy as np

    from benchmark.harness import costs_nemotron_h as costs

    share = np.stack([np.asarray(x).mean(0) for x in loads])  # [layers, held]
    uniform = costs.uniform_shares(model)
    reached = lambda shares: float(np.mean(
        [costs.held_experts_reached(model, rows, s) for s in shares]))
    return {"uniform_share": uniform[0],
            "share_least": float(share.min()),
            "share_median": float(np.median(share)),
            "share_largest": float(share.max()),
            "rows_routed_a_layer": float(rows * share.sum(-1).mean()),
            "rows_routed_uniform": rows * sum(uniform),
            "held_reached_a_layer": reached(share),
            "held_reached_uniform": reached([uniform])}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--probes", default="")
    ap.add_argument("--decode", type=int, default=0)
    ap.add_argument("--tag", default="nemotron_h_check")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--no-variants", action="store_true")
    ap.add_argument("--no-served", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.harness import device as dev
    from benchmark.harness import manifest
    from benchmark.reference import nemotron_h_variants as variants
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
            rng = np.random.default_rng([seed, 0x4E3A])
            caught = dict.fromkeys(
                tuple(n for n in names if n not in REPORTED)
                + (BELOW, DECODE_ONE_GROUP, DECODE_BELOW), False)
            # a decode-only fault must be caught by the decoded rows' limit
            decode_only = (DECODE_ONE_GROUP, DECODE_BELOW)
            kept = []   # (probe, tokens, decode, the right reference's rows,
            #              the chunked part's rows, its pools on the host,
            #              the table)

            def judge(name, got, want, kind, n, decode):
                seen = ref.held(got, want, decode)
                why = ref.disagreements(seen, limits)
                for key in ("logits_mean_abs_diff",
                            "decode_logits_mean_abs_diff"):
                    diffs.setdefault(key, {}).setdefault(name, []).append(
                        seen[key])
                say(part=kind, seed=seed, prompt=n, name=name, **seen,
                    why_not=why)
                if name == "right" and why:
                    wrong.append(f"seed {seed}, prompt {n}: the right "
                                 f"form: {why}")
                elif name in decode_only:
                    caught[name] |= len(why) == 1 and "decoded" in why[0]
                elif name in caught:
                    caught[name] |= bool(why)

            for n in probes:
                decode = args.decode or min(ref.DECODE_ROWS, n // 2)
                prompt = rng.integers(0, vocab, n).tolist()
                out = greedy(eng, prompt, decode)
                tokens = np.asarray(prompt + out, np.int32)
                pre, cache, table = program.prefill(model, tokens, n)
                pools = jax.device_get(cache)
                got = np.concatenate(
                    [pre, program.decode(model, tokens, n, cache, table)])
                rows = len(got)     # the chunked part's last rows, then the
                #                     decoded
                margins, loads = [], []
                right = ref.logits(model, weights, tokens, rows=rows,
                                   margins=margins, loads=loads)
                kept.append((n, tokens, decode, right, pre, pools, table))
                judge("right", got, right, "held", n, decode)
                say(part="load", seed=seed, prompt=n, **load_line(
                    model, [x[:len(tokens)] for x in loads]))
                # each judged row's reading beside its routing margin (in
                # router logits)
                say(part="margins", seed=seed, prompt=n, decode=decode,
                    rows=[round(float(r), 4) for r in
                          np.abs(got - right).mean(-1)],
                    margins=[round(float(m), 4) for m in ref.routing_margin(
                        margins, len(tokens))[-rows:] / ref.MARGIN_SCALE],
                    gaps=[round(float(g), 4) for g in right.max(-1) - right[
                        np.arange(rows), got.argmax(-1)]])
                gaps_all += (right.max(-1) - right[
                    np.arange(rows), got.argmax(-1)]).tolist()
                # the fault planted in the single-token call alone, over the
                # right program's pools (its jit is traced while the plant
                # is open: the first call)
                with one_group_update():
                    faulty = faulty or family.paged_call.__wrapped__(
                        program.cfg, program.dtype.name)
                    judge(DECODE_ONE_GROUP, np.concatenate([pre, program.decode(
                        model, tokens, n, jax.device_put(pools), table,
                        call=faulty)]), right, "program", n, decode)
                # the state's type: the same weights, the state in bfloat16
                # (reported; ``caught`` does not wait for it)
                low = family.Program(eng.params, role,
                                     options={"state_dtype": "bfloat16"})
                judge(STATE_BELOW, low.logits(model, tokens, decode),
                      right, "program", n, decode)
                del low
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
            # the precision controls LAST: the weights are rounded where they
            # lie, so the engine that served them is gone by then
            params = eng.params
            del eng, weights, program
            gc.collect()
            params = rounded_in_place(params, BELOW)
            below = family.Program(params, role)
            for n, tokens, decode, right, pre, pools, table in kept:
                judge(BELOW, below.logits(model, tokens, decode), right,
                      "program", n, decode)
                # fp8 in the single-token calls alone: the right program's
                # chunked rows and pools, the decode with rounded weights
                judge(DECODE_BELOW, np.concatenate([pre, below.decode(
                    model, tokens, n, jax.device_put(pools), table)]), right,
                    "program", n, decode)
            del below, params, kept
            gc.collect()
            wrong += [f"seed {seed}: {name} is inside the limit on every "
                      f"probe" for name, hit in caught.items() if not hit]
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
