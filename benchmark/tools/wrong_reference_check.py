#!/usr/bin/env python3
"""What a serving cell's comparison with its plain reference can see, for a
configuration whose reference comes with deliberately wrong variants
(``benchmark/reference/<name>_variants.py``: ``NAMES`` and ``logits``). Two
parts, many seeds, one process
(``chiprun -- python3 benchmark/tools/wrong_reference_check.py ...``):

``tokens``  ``probe_sweep.py``'s sweep - the cell's probes served through the
            engine, with longer answers - with every served token held
            against the right reference AND against each wrong one: how far
            below each one's top it lies.
``logits``  the program's own logits, outside the engine: ``apply_paged``
            through a chunked prefill and ``--decode`` single-token steps in
            the served precision over the cell's block geometry, against
            each reference's full forward at the served positions: mean and
            largest absolute difference.

    wrong_reference_check.py --workload W --seeds 11,12 [--logit-seeds 1,2]
        [--steps 48] [--decode 8] [--tag T] [--rehearse]

Nothing is timed and no result line is printed; every line also goes to
``chiprun_out/<tag>/<workload>.jsonl``, and a summary of both parts is the
last line.
"""

import argparse
import gc
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def serve(eng, prompt, steps: int, uid: int):
    """``closed_loop.probe_tokens``'s serving of one probe: the prompt by
    chunks if it is longer than one, then greedy steps."""
    out = []
    if len(prompt) > eng.config.split_prefill_chunk:
        eng.put_split(uid, prompt)
    else:
        out.append(int(eng.put(uid, prompt)))
    while len(out) < steps + 1:
        token = eng.step().get(uid)
        if token is not None:
            out.append(int(token))
    eng.finish(uid)
    return out


def paged_logits(cell, eng, prompt, steps: int):
    """Logits ``[steps + 1, vocab]`` and the greedy tokens of the program's
    ``apply_paged``: the prompt in padded chunks of the cell's SplitFuse
    size, then single tokens, over a pool of its own with the cell's block
    size and table width."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    module = cell.family.module()
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    ragged = cell.role["engine"]["ragged"]
    block, chunk = ragged["block_size"], cell.role["engine"]["split_prefill_chunk"]
    width = -(-cell.model["max_position_embeddings"] // block)
    need = -(-(len(prompt) + steps) // block)
    table = np.zeros((1, width), np.int32)
    table[0, :need] = 1 + np.arange(need)               # block 0 is the trash
    cache = module.init_paged_cache(cfg, need + 1, block,
                                    dtype=jnp.dtype(cell.role["weights_dtype"]))
    table = jnp.asarray(table)

    def call(params, cache, tokens, ctx, n_valid):
        valid = jnp.arange(tokens.shape[1])[None] < n_valid
        logits, cache = module.apply_paged(cfg, params, tokens, cache, table,
                                           ctx, valid=valid)
        return logits[0, n_valid - 1], cache

    call = jax.jit(call, donate_argnums=(1,))
    row = None
    for start in range(0, len(prompt), chunk):
        piece = prompt[start:start + chunk]
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :len(piece)] = piece
        row, cache = call(eng.params, cache, jnp.asarray(padded),
                          jnp.asarray([start], jnp.int32),
                          jnp.asarray(len(piece), jnp.int32))
    rows, tokens = [np.asarray(row)], [int(np.argmax(row))]
    for i in range(steps):
        row, cache = call(eng.params, cache,
                          jnp.asarray([[tokens[-1]]], jnp.int32),
                          jnp.asarray([len(prompt) + i], jnp.int32),
                          jnp.asarray(1, jnp.int32))
        rows.append(np.asarray(row))
        tokens.append(int(np.argmax(row)))
    return np.stack(rows), tokens


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--logit-seeds", default="")
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--decode", type=int, default=8)
    ap.add_argument("--tag", default="wrong_reference")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import numpy as np

    from benchmark.harness import device as dev
    from benchmark.harness import manifest
    from benchmark.traffic_kinds import closed_loop
    from benchmark.traffic_kinds.common import Run

    cell = manifest.Cell(args.workload, rehearsal=args.rehearse)
    variants = importlib.import_module(
        f"benchmark.reference.{cell.family.REFERENCE}_variants")
    dev.compile_cache_dir()
    device = dev.check(cell.chips, args.rehearse)
    out_dir = os.path.join(ROOT, "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    vocab = cell.model["vocab_size"]
    ints = lambda text: [int(s) for s in text.split(",") if s]
    seeds, logit_seeds = ints(args.seeds), ints(args.logit_seeds)
    gaps = {name: [] for name in ("right",) + tuple(variants.NAMES)}
    diffs = {name: [] for name in gaps}

    def references(eng, tokens):
        weights = cell.family.Weights(eng.params)
        yield "right", np.asarray(cell.reference.logits(cell.model, weights,
                                                        tokens))
        for name in variants.NAMES:
            yield name, np.asarray(variants.logits(name, cell.model, weights,
                                                   tokens))

    with open(os.path.join(out_dir, cell.name + ".jsonl"), "w") as f:
        def say(**line):
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")

        for seed in sorted(set(seeds) | set(logit_seeds)):
            t0 = time.perf_counter()
            eng = closed_loop.build(Run(
                cell=cell, seed=seed, seconds=0.0, trace=False,
                out_dir=out_dir, t_process=t0, device=device))
            rng = np.random.default_rng([seed, 0x9B0BE])   # the cell's probes
            for i, (n, _) in enumerate(cell.traffic["probes"]):
                prompt = rng.integers(0, vocab, n).tolist()
                if seed in seeds:
                    out = serve(eng, prompt, args.steps, uid=10 ** 6 + i)
                    tokens = np.asarray(prompt + out[:-1], np.int32)
                    line = {"part": "tokens", "seed": seed, "prompt": n}
                    for name, want in references(eng, tokens):
                        want = want[n - 1:]
                        below = want.max(-1) - want[np.arange(len(out)), out]
                        gaps[name] += below.tolist()
                        line[name] = [round(float(g), 4) for g in below]
                    say(**line)
                if seed in logit_seeds:
                    got, out = paged_logits(cell, eng, prompt, args.decode)
                    tokens = np.asarray(prompt + out[:-1], np.int32)
                    line = {"part": "logits", "seed": seed, "prompt": n,
                            "positions": len(out)}
                    for name, want in references(eng, tokens):
                        d = np.abs(got - want[n - 1:])
                        diffs[name].append((float(d.mean()), float(d.max())))
                        line[name] = {"mean_abs_diff": float(d.mean()),
                                      "max_abs_diff": float(d.max())}
                    line["logit_std"] = float(got.std())
                    say(**line)
            del eng
            gc.collect()
            say(seed=seed, seconds=time.perf_counter() - t0)
        summary = {"part": "summary", "workload": cell.name,
                   "device": device, "tokens": {}, "logits": {}}
        for name, g in gaps.items():
            if g:
                g = np.asarray(g)
                summary["tokens"][name] = {
                    "positions": len(g), "largest_gap": float(g.max()),
                    "p99": float(np.percentile(g, 99)),
                    "p90": float(np.percentile(g, 90)),
                    "p50": float(np.percentile(g, 50)),
                    "at_the_top": int((g == 0).sum()),
                    "beyond_0.1": int((g > 0.1).sum()),
                    "beyond_0.3": int((g > 0.3).sum()),
                    "share_beyond_tol": float(
                        (g > closed_loop.SERVED_TOKEN_GAP_TOL).mean())}
        for name, d in diffs.items():
            if d:
                d = np.asarray(d)
                summary["logits"][name] = {
                    "prompts": len(d), "mean_abs_diff": float(d[:, 0].mean()),
                    "largest_mean_abs_diff": float(d[:, 0].max()),
                    "least_mean_abs_diff": float(d[:, 0].min()),
                    "max_abs_diff": float(d[:, 1].max())}
        right = summary["logits"].get("right")
        for name, s in summary["logits"].items():
            if right and name != "right":
                s["least_over_rights_largest"] = \
                    s["least_mean_abs_diff"] / right["largest_mean_abs_diff"]
        say(**summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
