#!/usr/bin/env python
"""Does the program still start on the chip? The quickest proof there is.

    python chip_smoke.py            # one TPU chip: device -> train -> serve
    python chip_smoke.py --chips 4  # four chips: ZeRO-3 data=4 against one device

Drives the two main paths once, through the entry points a user calls, at the
published widths of Mistral-7B (hidden 4096, FFN 14336, 32 query / 8 KV heads
of 128, vocabulary 32000, rope theta 10000 - ``LlamaConfig.mistral_7b``).
Depth is the only cut, the weights are random from ``--seed``:

- **train**: ``deepspeed_tpu.initialize`` + ``engine.train_batch`` with the
  ZeRO-3 / bf16 / AdamW / clipping config ``bench.py`` uses;
- **serve**: ``build_engine_v2`` + ``ServingScheduler`` answering a handful of
  requests, then prefill + paged-decode logits against one dense forward.

One process, one JAX client. Without a TPU it fails at once: there is no CPU
branch. Every line printed is one JSON object; what the phases print is a
smoke log (times on the host clock, ended by ``block_until_ready``), not a
benchmark. The LAST line is the verdict the driver reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
import traceback

MOSAIC = "tpu_custom_call"  # how a Pallas kernel Mosaic compiled shows in HLO

# bf16 tolerances, each with its reason -------------------------------------
# Logits of a random-weight model have about unit variance (unit-RMS final
# norm times a 1/sqrt(h) head). The served path and the dense forward do the
# same bf16 arithmetic in a different order (paged decode / paged prefill
# kernels against flash attention), so they differ by accumulated bf16 rounding:
# 2^-9 relative per op over a few ops in each of 16 layers is about 1-2% of
# a unit-variance logit in the mean, a few times that in the worst of 32000
# entries. A wrong block, offset or mask moves logits by order 1.
LOGIT_MEAN_ABS_TOL = 0.05
LOGIT_MAX_ABS_TOL = 0.5
# ZeRO-3 over four chips reduces gradients in another order than one chip
# does, in bf16 compute; the loss near ln(32000) = 10.4 carries about three
# significant digits through three optimizer steps.
LOSS_4_VS_1_TOL = 0.05


class SmokeFailure(AssertionError):
    """A phase saw something wrong. Never caught into an exit 0."""


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------------------- #
# sizes: what one v5e chip (16 GB) holds at the published widths
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class TrainSize:
    # 2 of 32 layers: fp32 masters + two Adam moments are 12 B a parameter
    # and both embeddings stay whole (698 M parameters, 8.4 GB of state);
    # at batch 4 the compiled step needs 13.9 GB (rehearsed)
    layers: int = 2
    batch: int = 4
    seq: int = 2048
    steps: int = 4


@dataclasses.dataclass(frozen=True)
class ServeSize:
    layers: int = 16              # of 32: 7.5 GB of bf16 weights
    block_size: int = 32
    # 28.7k tokens of KV, 1.9 GB in bf16. Not "the rest of the chip": the
    # size dates from when every paged program held a SECOND copy of the pool
    # as scan temporaries (until ISSUE 29 made the pools the layer scan's
    # carry) and a 4 x 256-token prefill held 4 GB of f32 scores over the
    # table's width besides: 15.4 GB compiled then
    pool_blocks: int = 896
    slots: int = 32               # decode batch width
    prefill_chunk: int = 256      # Dynamic-SplitFuse chunk for long prompts
    prompt_lens: tuple = (128, 200, 256, 384, 512, 640, 768, 1024)
    # a prompt admitted BESIDE those streams, most of its chunk padding
    late_prompt: int = 100
    new_tokens: int = 64
    # the two logit checks: (prompt length, decode steps before the probe);
    # one short prompt through the batched prefill, one long through chunks
    probes: tuple = ((200, 8), (1000, 8))


def widths(cfg) -> dict:
    return {"hidden": cfg.hidden_size, "ffn": cfg.intermediate_size,
            "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_size, "vocab": cfg.vocab_size,
            "rope_theta": cfg.rope_theta, "max_seq_len": cfg.max_seq_len}


def mistral_7b(layers: int, **kw):
    from deepspeed_tpu.models import llama

    return dataclasses.replace(llama.LlamaConfig.mistral_7b(),
                               num_layers=layers, **kw)


# --------------------------------------------------------------------------- #
# phase 1: device
# --------------------------------------------------------------------------- #
def phase_device(want_chips: int) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    from deepspeed_tpu.tuning.persist import tuned_path
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    devs = jax.devices()  # raises if the configured platform cannot start
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(phase="device", jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=md.version("libtpu"), compile_cache=enable_compile_cache(),
        **device)
    require(device["platform"] == "tpu",
            f"no TPU: JAX reports platform {device['platform']!r}")
    require(device["count"] == want_chips,
            f"{device['count']} chips visible, this run is for {want_chips} "
            f"(four chips: --chips 4)")
    # What the program compiles must come from files git would commit. The
    # flash kernel reads its block size from an untracked tuned file when
    # one is lying in the tree, so that file fails the smoke.
    require(not os.path.exists(tuned_path()),
            f"{tuned_path()} is steering the flash block sizes; it is not "
            f"part of any commit - remove it")
    say(phase="device", flash_block=fa._block(1 << 20),
        flash_block_env=os.environ.get("DSTPU_FLASH_BLOCK"),
        ops=registry.resolved())  # op -> the implementation every call gets
    return device


# --------------------------------------------------------------------------- #
# phase 2: train
# --------------------------------------------------------------------------- #
def train_config(batch: int) -> dict:
    """The config ``bench.py`` trains with, plus the recompile sentinel."""
    return {
        "train_batch_size": batch,
        "bf16": {"enabled": True},
        "optimizer": {"type": "adamw",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": 3},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
        "comms_logger": {"enabled": True},
        "telemetry": {"compile": {"enabled": True}},
    }


def build_trainer(cfg, batch: int, seed: int, devices=None):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import llama

    mesh_lib.set_mesh(None)
    engine, _, _, _ = dst.initialize(
        model=llama.model_spec(cfg, compute_dtype=jnp.bfloat16),
        config=train_config(batch), rng=jax.random.PRNGKey(seed),
        devices=devices)
    return engine


def run_steps(engine, cfg, batch: int, seq: int, steps: int, seed: int):
    """``steps`` optimizer steps on ONE repeated seeded batch."""
    import jax
    import numpy as np

    tokens = {"tokens": np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)}
    losses, norms, times = [], [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = engine.train_batch(tokens)
        jax.block_until_ready(out.loss)
        times.append(time.perf_counter() - t0)
        losses.append(float(out.loss))
        norms.append(float(out.grad_norm))
    return losses, norms, times


def compiled_programs(monitored) -> list:
    """The executables the compile monitor built for one jitted entry."""
    return list(monitored._compiled.values())


def compiled_bytes(compiled) -> dict:
    mem = compiled.memory_analysis()
    return {"arguments": mem.argument_size_in_bytes,
            "aliased": mem.alias_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes}


def peak_bytes_in_use():
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def cut(size) -> dict:
    """What was cut from the published configuration: depth, and for the
    trainer the batch and sequence it was run at."""
    out = {"layers": f"{size.layers} of 32"}
    if isinstance(size, TrainSize):
        out.update(batch=size.batch, seq=size.seq)
    return out


def check_train(cfg, losses, norms, stats, hlo: str, mosaic: bool) -> None:
    require(all(map(math.isfinite, losses + norms)),
            f"non-finite loss or gradient norm: {losses} {norms}")
    # Random-init logits have unit variance (see the tolerances above), so
    # the expected first loss is E[logsumexp] - E[logit] = ln(vocab) + 1/2,
    # 10.87 at 32000 - the upper edge of "within 0.5 of ln(vocab)", which is
    # why the band is centred there. Averaged over 8192 tokens it moves in
    # the second decimal.
    expected = math.log(cfg.vocab_size) + 0.5
    require(abs(losses[0] - expected) < 0.25,
            f"first loss {losses[0]:.3f} is not within 0.25 of ln(vocab) + "
            f"1/2 = {expected:.3f}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    # runtime/engine.py (scalar out_shardings at init) says why a second
    # compilation of the step is a bug, not warm-up
    require(stats["compiles"] == 1 and stats["recompiles"] == 0,
            f"train step compiled {stats['compiles']} times")
    if mosaic:
        require(hlo.count(MOSAIC) > 0,
                "no Mosaic kernel in the train step: attention and the norms "
                "ran the XLA reference, not Pallas")


def phase_train(size: TrainSize, seed: int, mosaic: bool = True,
                cfg=None) -> None:
    cfg = cfg or mistral_7b(size.layers, remat=True)
    engine = build_trainer(cfg, size.batch, seed)
    losses, norms, times = run_steps(engine, cfg, size.batch, size.seq,
                                     size.steps, seed)
    stats = engine.telemetry.compile.summary()["train_step"]
    compiled = compiled_programs(engine._train_step)[0]
    hlo = compiled.as_text()
    say(phase="train", widths=widths(cfg), params=cfg.num_params,
        cut=cut(size), losses=losses, grad_norms=norms,
        compile_s=(stats["lower_ms"] + stats["compile_ms"]) / 1e3,
        first_step_s=times[0], step_s=times[1:],
        compiles=stats["compiles"], mosaic_calls=hlo.count(MOSAIC),
        compiled_bytes=compiled_bytes(compiled),
        peak_bytes_in_use=peak_bytes_in_use())
    check_train(cfg, losses, norms, stats, hlo, mosaic)
    engine.destroy()


# --------------------------------------------------------------------------- #
# phase 3: serve
# --------------------------------------------------------------------------- #
def build_server(cfg, size: ServeSize, seed: int):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.engine_v2 import build_engine_v2
    from deepspeed_tpu.models import llama

    # one jitted init straight to bf16: no fp32 copy of 3.7 B weights
    params = jax.jit(lambda k: llama.init(cfg, k, dtype=jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    return build_engine_v2(
        llama, cfg, params,
        config={"dtype": "bfloat16",
                "prefill_bucket": min(64, size.prefill_chunk),
                "split_prefill_chunk": size.prefill_chunk,
                # every bucket of every program family is expected warm-up
                "compile_monitor": {"enabled": True,
                                    "warmup_signatures": 64},
                "ragged": {"max_tracked_sequences": size.slots,
                           "max_ragged_batch_size": size.slots,
                           "memory_config_blocks": size.pool_blocks,
                           "block_size": size.block_size}})


def serve_pass(eng, prompts, new_tokens: int):
    """Submit every prompt, tick the scheduler dry - the loop
    ``scripts/serving_bench.py`` runs. Returns (handles, seconds)."""
    from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                                 ServingScheduler)

    sched = ServingScheduler(eng, SchedulerConfig(decode_quantum=1))
    t0 = time.perf_counter()
    handles = [sched.submit(Request(prompt=p, max_new_tokens=new_tokens))
               for p in prompts]
    while sched.pending:
        sched.tick()
    return handles, time.perf_counter() - t0


def synchronous(sched):
    """The tick as it was before a program stayed in flight: the engine's
    ``step()`` - launch, then collect at once, what ``generate()`` and the
    logit probes below call - and the tick returns its own program's
    tokens."""
    eng = sched.engine

    def step_engine(seed):
        if not eng.state.seqs:
            return {}, {"decode_seqs": 0, "kv_tokens": 0}
        return eng.step(seed=seed), eng.last_step

    sched._step_engine = step_engine
    return sched


def check_overlap(eng, prompts, late_prompt, new_tokens: int,
                  greedy) -> dict:
    """The streams of the ticks as shipped (program n+1 launched before
    program n's tokens are read, the tokens resolved on the device) against
    the same requests through synchronous ``step()`` ticks with the same
    seeds: every stream token for token. Every other request is sampled (its
    own row of the ``_dyn`` programs); request 0 ends on an ``eos_token_id``
    taken from the middle of the stream it has without one, so a row
    launched past its end is dropped; request 1 is preempted with a token in
    flight and resumed; the long prompts' first tokens are seated from the
    final chunk's result; ``late_prompt``, shorter than a chunk, comes in
    beside the live streams: the overlapped tick finds a program in flight
    and so admits it through the chunk lane - a first-and-final chunk in
    the tick's one program, no ``put`` drain. Then fused quanta
    (``decode_quantum=4``: each drains what is in flight) against
    ``greedy``, the single steps' streams of the same prompts, and a quantum
    of ONE tick."""
    from deepspeed_tpu.inference.sampling import SamplingParams
    from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                                 ServingScheduler)

    hot = SamplingParams(temperature=0.8, top_k=40)

    def streams(sched, eos=None, lane_late=False):
        # a sampled row's noise is its SLOT's (one categorical draw over
        # [slots, vocab]): every pass seats its requests in the same slots
        eng.state._free_slots.sort(reverse=True)
        handles = [sched.submit(Request(
            prompt=p, max_new_tokens=new_tokens,
            eos_token_id=eos if i == 0 else None,
            **({"sp": hot} if i % 2 else {})))
            for i, p in enumerate(prompts)]
        before, put_drains = eng.overlapped_steps, eng.drains["put"]
        ticks = 0
        while sched.pending:
            if ticks == 3:      # the tick after request 1 resumes
                handles.append(sched.submit(Request(
                    prompt=late_prompt, max_new_tokens=new_tokens)))
                if lane_late:
                    # nothing is ever in flight under synchronous ticks, so
                    # they would run this prompt as a one-shot ``prefill``,
                    # ANOTHER program, which rounds a bf16 near-tie its own
                    # way: told to take the lane (it is the run's last
                    # admission), they run it in the mixed program the
                    # overlapped tick runs it in
                    sched._takes_chunk_lane = lambda n: True
            sched.tick()
            ticks += 1
            if ticks == 2:
                sched.preempt(handles[1].uid)
        require(all(h.state == "done" for h in handles),
                f"requests ended {[h.state for h in handles]}")
        split = sum(len(p) > eng.config.split_prefill_chunk for p in prompts)
        require(sched.stats["chunked_admissions"] == split + 1,
                f"the late prompt of {len(late_prompt)} tokens was not "
                f"admitted through the chunk lane")
        return ([h.tokens for h in handles], ticks,
                eng.overlapped_steps - before, sched.stats["preempted"],
                eng.drains["put"] - put_drains)

    new = lambda: ServingScheduler(eng, SchedulerConfig())  # noqa: E731
    whole = streams(synchronous(new()), lane_late=True)[0][0]
    eos = whole[new_tokens // 2]
    want, sync_ticks, sync_over, *_ = streams(synchronous(new()), eos,
                                              lane_late=True)
    got, ticks, overlapped, preempted, put_drains = streams(new(), eos)
    require(put_drains == 0, f"{put_drains} admissions of the overlapped "
            f"ticks read the program in flight (cause put)")
    differ = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
    require(not differ, f"overlapped ticks stream other tokens than "
            f"synchronous steps for requests {differ}: "
            f"{[(got[i], want[i]) for i in differ[:2]]}")
    require(sync_over == 0 and overlapped > 0 and preempted == 1,
            f"overlapped_steps {sync_over} through step(), {overlapped} "
            f"through the ticks; {preempted} preemptions")
    require(got[0] == whole[:whole.index(eos) + 1],
            f"the eos stream is {got[0]}, without its eos {whole}")
    require(all(len(t) == new_tokens for t in got[1:]),
            f"stream lengths {[len(t) for t in got]}")

    # fused quanta: another compiled program (a scan of the tick), so equal
    # streams are reported and not required at bf16 with random weights
    sched = ServingScheduler(eng, SchedulerConfig(decode_quantum=4))
    handles = [sched.submit(Request(prompt=p, max_new_tokens=new_tokens))
               for p in prompts]
    sched.run()
    require(all(h.state == "done" and len(h.tokens) == new_tokens
                for h in handles), "a fused-quantum stream ended short")
    one = {}
    for name, step in (("quantum", lambda: eng.step_many(1, seed=7)[9][0]),
                       ("step", lambda: eng.step(seed=7)[9])):
        eng.put(9, prompts[0])
        one[name] = [step() for _ in range(3)]
        eng.finish(9)
    require(one["quantum"] == one["step"],
            f"a quantum of one tick gives {one}")
    require(eng.in_flight == 0 and not eng.state.seqs,
            "the engine did not end empty")
    return {"requests": len(got), "sampled": len(prompts) // 2,
            "streams_equal": len(got) - len(differ),
            "late_prompt_tokens": len(late_prompt), "put_drains": put_drains,
            "eos_stream_tokens": len(got[0]), "preempted": preempted,
            "ticks": ticks, "synchronous_ticks": sync_ticks,
            "overlapped_steps": overlapped,
            # tokens before a fused-quantum stream first leaves its
            # single-step stream (``new_tokens``: never)
            "quantum_tokens_as_greedy": [
                next((j for j, (a, b) in enumerate(zip(h.tokens, g))
                      if a != b), new_tokens)
                for h, g in zip(handles, greedy)]}


def total_compiles(eng) -> int:
    return sum(int(s["compiles"])
               for s in eng.compile_monitor.summary().values())


def probe_logits(eng, cfg, prompt, steps: int, uid: int):
    """Prefill ``prompt`` and decode ``steps`` tokens through the engine,
    then ask for the logits of the last cached position twice: from the
    engine's own pool and block table by a direct ``apply_paged`` call
    (which rewrites that position's KV with the value it already holds and
    reads every earlier position as the engine's prefill and paged decode
    left it), and from one dense ``llama.apply`` over the same tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import llama
    from deepspeed_tpu.ops import registry

    if len(prompt) > eng.config.split_prefill_chunk:
        eng.put_split(uid, prompt)   # chunked, as the scheduler admits it
    else:
        eng.put(uid, prompt)
    while len(eng.state.seqs[uid].generated) < steps + 1:
        eng.step()
    d = eng.state.seqs[uid]
    tokens = np.asarray(d.tokens, np.int32)       # KV positions [0, seen)
    require(len(tokens) == d.seen_tokens == len(prompt) + steps,
            f"probe {uid}: engine cached {d.seen_tokens} tokens")

    def last_position(params, cache, tok, table, ctx):
        logits, cache = llama.apply_paged(cfg, params, tok, cache, table, ctx)
        return logits[0, 0], cache

    paged, eng.cache = jax.jit(last_position, donate_argnums=(1,))(
        eng.params, eng.cache, jnp.asarray(tokens[None, -1:]),
        jnp.asarray(eng.state.block_table(d)[None]),
        jnp.asarray([d.seen_tokens - 1], jnp.int32))
    # the reference is the plain XLA softmax attention, not the flash kernel
    registry.set_backend("attention", "xla")
    try:
        with jax.default_matmul_precision("highest"):
            dense = jax.jit(lambda p, t: llama.apply(cfg, p, t)[0, -1])(
                eng.params, jnp.asarray(tokens[None]))
    finally:
        registry.set_backend("attention", None)
    paged, dense = np.asarray(paged), np.asarray(dense)
    eng.finish(uid)
    diff = np.abs(paged - dense)
    return {"prompt": len(prompt), "decoded": steps,
            "mean_abs_diff": float(diff.mean()),
            "max_abs_diff": float(diff.max()),
            "logit_std": float(dense.std()),
            "argmax_equal": bool(paged.argmax() == dense.argmax()),
            "argmax_is_served_token": bool(paged.argmax() == d.last_token)}


def phase_serve(size: ServeSize, seed: int, mosaic: bool = True,
                cfg=None) -> None:
    import jax
    import numpy as np

    from deepspeed_tpu.comm import mesh as mesh_lib

    cfg = cfg or mistral_7b(size.layers)
    mesh_lib.set_mesh(None)  # the server builds its own over every device
    t0 = time.perf_counter()
    eng = build_server(cfg, size, seed)
    jax.block_until_ready(eng.params)
    build_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in size.prompt_lens]

    # first pass compiles every program; the second is the same traffic warm
    first, first_s = serve_pass(eng, prompts, size.new_tokens)
    warm_compiles = total_compiles(eng)
    handles, dt = serve_pass(eng, prompts, size.new_tokens)
    for h in first + handles:
        require(h.state == "done" and len(h.tokens) == size.new_tokens,
                f"request {h.uid} ({len(h.request.prompt)} prompt tokens) "
                f"ended {h.state} with {len(h.tokens)} of "
                f"{size.new_tokens} tokens: {h.error}")
    require(all(a.tokens == b.tokens for a, b in zip(first, handles)),
            "greedy streams differ between two passes over the same prompts")
    require(total_compiles(eng) == warm_compiles,
            f"the warm pass compiled {total_compiles(eng) - warm_compiles} "
            f"new programs")

    probes = [probe_logits(eng, cfg,
                           rng.integers(0, cfg.vocab_size, n).tolist(),
                           steps, uid=10 ** 6 + i)
              for i, (n, steps) in enumerate(size.probes)]
    decode = eng._decode_fn(1, False)    # the program the passes ran
    decode_hlo = "\n".join(c.as_text() for c in compiled_programs(decode))
    pool_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.cache))
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    say(phase="serve", widths=widths(cfg), params=cfg.num_params,
        cut=cut(size), weight_bytes=weight_bytes, kv_pool_bytes=pool_bytes,
        kv_pool_tokens=size.pool_blocks * size.block_size,
        requests=len(handles), prompt_lens=list(size.prompt_lens),
        new_tokens=size.new_tokens, build_s=build_s,
        first_pass_s=first_s, warm_pass_s=dt,
        warm_tokens_per_s=sum(len(h.tokens) for h in handles) / dt,
        compiles={k: int(v["compiles"])
                  for k, v in eng.compile_monitor.summary().items()},
        decode_mosaic_calls=decode_hlo.count(MOSAIC),
        decode_compiled_bytes=compiled_bytes(compiled_programs(decode)[0]),
        logit_checks=probes, peak_bytes_in_use=peak_bytes_in_use())
    for p in probes:
        require(p["mean_abs_diff"] <= LOGIT_MEAN_ABS_TOL
                and p["max_abs_diff"] <= LOGIT_MAX_ABS_TOL,
                f"prefill + paged decode disagrees with the dense forward: "
                f"{p}")
    if mosaic:
        require(decode_hlo.count(MOSAIC) > 0,
                "no Mosaic kernel in the decode step: paged attention ran "
                "the XLA reference")
    say(phase="serve_overlap",
        **check_overlap(eng, prompts,
                        rng.integers(0, cfg.vocab_size,
                                     size.late_prompt).tolist(),
                        size.new_tokens, [h.tokens for h in handles]))


# --------------------------------------------------------------------------- #
# four chips: ZeRO-3 over data=4 against the same job on one device
# --------------------------------------------------------------------------- #
def state_bytes_per_device(engine) -> dict:
    """Bytes of fp32 masters + optimizer state each device really holds."""
    import jax

    held: dict = {}
    for leaf in jax.tree.leaves((engine.state.params,
                                 engine.state.opt_state)):
        for shard in leaf.addressable_shards:
            held[str(shard.device)] = held.get(str(shard.device), 0) \
                + shard.data.nbytes
    return held


def phase_four_chips(size: TrainSize, seed: int, mosaic: bool = True,
                     cfg=None) -> None:
    import jax

    cfg = cfg or mistral_7b(size.layers, remat=True)
    devs = jax.devices()

    sharded = build_trainer(cfg, size.batch, seed)
    held = state_bytes_per_device(sharded)
    logical = sum(x.nbytes for x in jax.tree.leaves(
        (sharded.state.params, sharded.state.opt_state)))
    losses4, norms4, times4 = run_steps(sharded, cfg, size.batch, size.seq, 3,
                                        seed)
    hlo = compiled_programs(sharded._train_step)[0].as_text()
    collectives = {op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
                   for op in ("all-gather", "reduce-scatter", "all-reduce")}
    stats4 = sharded.telemetry.compile.summary()["train_step"]
    sharded.destroy()
    del sharded
    gc.collect()

    single = build_trainer(cfg, size.batch, seed, devices=[devs[0]])
    losses1, norms1, times1 = run_steps(single, cfg, size.batch, size.seq, 3,
                                        seed)
    hlo1 = compiled_programs(single._train_step)[0].as_text()
    single.destroy()

    say(phase="four_chips", widths=widths(cfg), params=cfg.num_params,
        cut=cut(size), mesh={"data": len(devs)}, losses_data4=losses4, losses_one=losses1,
        loss_tolerance=LOSS_4_VS_1_TOL, state_bytes_logical=logical,
        state_bytes_per_device=held, collectives=collectives,
        mosaic_calls_data4=hlo.count(MOSAIC),
        mosaic_calls_one=hlo1.count(MOSAIC), compiles=stats4["compiles"],
        step_s_data4=times4[1:], step_s_one=times1[1:])
    check_train(cfg, losses4, norms4, stats4, hlo, mosaic)
    # every kernel of the one-device step is in the four-chip step too: an
    # op that ran the XLA reference there would be missing from the count
    require(hlo.count(MOSAIC) == hlo1.count(MOSAIC),
            f"{hlo.count(MOSAIC)} Mosaic kernels in the data=4 step, "
            f"{hlo1.count(MOSAIC)} in the one-device step")
    require(all(map(math.isfinite, losses1 + norms1)),
            f"non-finite loss or gradient norm on one device: {losses1}")
    worst = max(abs(a - b) for a, b in zip(losses4, losses1))
    require(worst <= LOSS_4_VS_1_TOL,
            f"data=4 and one-device losses differ by {worst:.4f}: "
            f"{losses4} vs {losses1}")
    require(len(held) == len(devs), f"state lives on {sorted(held)} only")
    for dev, n in held.items():
        share = n / logical
        require(0.8 / len(devs) <= share <= 1.2 / len(devs),
                f"{dev} holds {share:.2f} of the ZeRO-3 state, not about "
                f"1/{len(devs)}: {held}")
    require(collectives["all-gather"] > 0,
            f"no all-gather in the ZeRO-3 step: {collectives}")
    require(collectives["reduce-scatter"] + collectives["all-reduce"] > 0,
            f"no gradient reduction in the ZeRO-3 step: {collectives}")


# --------------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the ZeRO-3 data=4 step and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = None
    try:
        device = phase_device(args.chips)
        if args.chips == 4:
            phase_four_chips(TrainSize(), args.seed)
        else:
            phase_train(TrainSize(), args.seed)
            gc.collect()  # the trainer's state leaves the chip first
            phase_serve(ServeSize(), args.seed)
    except Exception as e:  # reported, then failed: never an exit 0
        traceback.print_exc()
        say(ok=False, device=device, error=f"{type(e).__name__}: {e}"[-2000:])
        return 1
    say(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
