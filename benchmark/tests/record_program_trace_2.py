"""Records the third small trace kept beside the tests
(``recorded_program_2.xplane.pb`` and, beside it, ``recorded_program_2.json``:
what was made): one program in flight, as PR 35 made the serving tick, with
the sequence numbers and the drain span of PR 36 on the program's own spans.

Run on the chip, once:

    chiprun --chips 1 -- python3 benchmark/tests/record_program_trace_2.py

Six rounds of a hand-made serving tick through the program's own ``Tracer``
(ring off, timeline on), a program of ~7 ms (``jit_decode``: ten 4096-wide
matmuls) launched before the session opens. A tick: ``sched_admit`` (sleeps
0.5 ms), ``sched_step_engine`` > LAUNCH ``decode_step{seq, overlapped}`` >
``engine_prep`` (0.3 ms), ``engine_dispatch`` (the call), then READ the
program before: ``engine_wait{seq}`` (blocks on it), ``engine_emit``
(0.4 ms); ``sched_harvest`` (0.2 ms). The FOURTH tick's admission drains:
``engine_drain{cause=put}`` > wait + emit of what is in flight, then a
one-shot ``prefill_batch{seq}`` (``jit_prefill``: three matmuls) with all
four children, so its launch finds nothing in flight (``overlapped=0``)
and its collect nothing to read. ``sched_tick`` carries ``drains``. The
benchmark's own ``tick`` span lies around each tick and ``harvest`` (1 ms)
between them, inside one ``window``; the last program is read after the
session closed.
"""

import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness.spans import Spans  # noqa: E402
from deepspeed_tpu.telemetry.trace import Tracer  # noqa: E402

NAME = "recorded_program_2"
SLEEP_MS = {"admit": 0.5, "prep": 0.3, "emit": 0.4, "harvest": 0.2}
TICKS, DRAINING = 6, 3          # the fourth tick drains


def main() -> None:
    out = os.path.join(ROOT, "chiprun_out", "record_program_trace_2")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    x = jnp.full((4096, 4096), 0.001, jnp.bfloat16)

    @jax.jit
    def decode(a):
        with jax.named_scope("ffn"):
            return jax.lax.fori_loop(0, 10, lambda i, c: (c @ a) * 0.01, a)

    @jax.jit
    def prefill(a):
        with jax.named_scope("ffn"):
            return jax.lax.fori_loop(0, 3, lambda i, c: (c @ a) * 0.01, a)

    decode(x).block_until_ready()
    prefill(x).block_until_ready()
    spans = Spans()
    tracer = Tracer(None, annotate=jax.profiler.TraceAnnotation)
    span = lambda name, **kw: tracer.span(name, cat="serving", **kw)
    nap = lambda what: time.sleep(SLEEP_MS[what] / 1e3)
    seq = [0]
    flight = []                 # (seq, result) of what is launched, unread

    def launch():
        seq[0] += 1
        with span("decode_step", seq=seq[0], batch=2,
                  overlapped=int(bool(flight))):
            with span("engine_prep"):
                nap("prep")
            with span("engine_dispatch"):
                y = decode(x)
        flight.append((seq[0], y))

    def read(n, y):
        with span("engine_wait", seq=n):
            y.block_until_ready()
        with span("engine_emit"):
            nap("emit")

    launch()                    # in flight when the session opens
    made = {"sleep_ms": SLEEP_MS, "drains": [], "decode_seqs": []}
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    with spans.span("window"):
        for i in range(TICKS):
            with spans.span("tick"), span("sched_tick", tick=i) as tick:
                drains = 0
                with span("sched_admit"):
                    nap("admit")
                    if i == DRAINING:
                        drains = 1
                        with span("engine_drain", cause="put"):
                            while flight:
                                read(*flight.pop(0))
                        seq[0] += 1
                        with span("prefill_batch", seq=seq[0], n=1):
                            with span("engine_prep"):
                                nap("prep")
                            with span("engine_dispatch"):
                                y = prefill(x)
                            read(seq[0], y)
                with span("sched_step_engine"):
                    launch()
                    while len(flight) > 1:
                        read(*flight.pop(0))
                with span("sched_harvest"):
                    nap("harvest")
                tick.set(decode_seqs=2, prefill_tokens=16 * (i == DRAINING),
                         kv_tokens=1000 + i, drains=drains)
            made["drains"].append(drains)
            made["decode_seqs"].append(seq[0])
            with spans.span("harvest"):
                time.sleep(0.001)
    jax.profiler.stop_trace()
    read(*flight.pop(0))
    path = glob.glob(os.path.join(out, "plugins/profile/*/*.xplane.pb"))[0]
    print("xplane bytes", os.path.getsize(path))
    shutil.copy(path, os.path.join(out, NAME + ".xplane.pb"))
    shutil.rmtree(os.path.join(out, "plugins"))
    with open(os.path.join(out, NAME + ".json"), "w") as f:
        json.dump(made, f, indent=1)
    print(json.dumps(made))
    from jax.profiler import ProfileData
    data = ProfileData.from_file(os.path.join(out, NAME + ".xplane.pb"))
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            show = evs[:8] if plane.name.startswith("/device") else \
                [e for e in evs if e.name.startswith(("bench:", "dstpu:"))][:40]
            for e in show:
                print("     ", e.name[:60], e.start_ns, e.duration_ns,
                      {k: str(v)[:60] for k, v in e.stats})


if __name__ == "__main__":
    main()
