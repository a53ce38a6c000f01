"""Records the second small trace kept beside the tests
(``recorded_program_1.xplane.pb``): the program's own spans, a named kernel
and named scopes, as PR 24 put them on the profiler's timeline.

Run on the chip, once:

    chiprun --chips 1 -- python3 benchmark/tests/record_program_trace.py

Three rounds of a hand-made serving tick, through the program's own
``Tracer`` (ring off, timeline on): ``sched_tick`` with ``sched_admit``
(sleeps 0.5 ms), ``sched_step_engine`` > ``decode_step`` > ``engine_prep``
(0.3 ms), ``engine_dispatch`` (the call into one jitted program),
``engine_wait`` (blocks on it), ``engine_emit`` (0.4 ms), and
``sched_harvest`` (0.2 ms); the tick's counts are set on the span at its
end. The benchmark's own ``tick`` span lies around each and a ``harvest``
span (1 ms) between them, inside one ``window``. The program is a matmul
and a Pallas kernel named ``paged_decode`` under the scope ``attn`` and a
``while`` of three matmuls under ``ffn``. It also prints the stats of a few
device events, which is how it was learnt that the TPU profiler gives them
no ``op_name``; the compiled program's text is kept beside the trace
(``recorded_program_1.hlo.txt``) for that.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness.spans import Spans  # noqa: E402
from deepspeed_tpu.telemetry.trace import Tracer  # noqa: E402

NAME = "recorded_program_1.xplane.pb"
HLO = "recorded_program_1.hlo.txt"


def main() -> None:
    out = os.path.join(ROOT, "chiprun_out", "record_program_trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    x = jnp.ones((2048, 2048), jnp.bfloat16)

    def add_kernel(a_ref, o_ref):
        o_ref[...] = a_ref[...] + 1

    @jax.jit
    def decode(a):
        with jax.named_scope("attn"):
            b = a @ a
            b = pl.pallas_call(add_kernel, out_shape=jax.ShapeDtypeStruct(
                b.shape, b.dtype), name="paged_decode")(b)
        with jax.named_scope("ffn"):
            return jax.lax.fori_loop(0, 3, lambda i, c: (c @ a) * 0.001, b)

    decode(x).block_until_ready()
    # the TPU profiler gives a device event no op_name: the scopes come from
    # the compiled program's text, kept beside the trace
    with open(os.path.join(out, HLO), "w") as f:
        f.write(decode.lower(x).compile().as_text())
    spans = Spans()
    tracer = Tracer(None, annotate=jax.profiler.TraceAnnotation)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    with spans.span("window"):
        for i in range(3):
            with spans.span("tick"), \
                    tracer.span("sched_tick", cat="serving", tick=i) as tick:
                with tracer.span("sched_admit", cat="serving"):
                    time.sleep(0.0005)
                with tracer.span("sched_step_engine", cat="serving"):
                    with tracer.span("decode_step", cat="serving", batch=2):
                        with tracer.span("engine_prep", cat="serving"):
                            time.sleep(0.0003)
                        with tracer.span("engine_dispatch", cat="serving"):
                            y = decode(x)
                        with tracer.span("engine_wait", cat="serving"):
                            y.block_until_ready()
                        with tracer.span("engine_emit", cat="serving"):
                            time.sleep(0.0004)
                with tracer.span("sched_harvest", cat="serving"):
                    time.sleep(0.0002)
                tick.set(decode_seqs=2, prefill_tokens=16 * i,
                         kv_tokens=1000 + i)
            with spans.span("harvest"):
                time.sleep(0.001)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins/profile/*/*.xplane.pb"))[0]
    print("xplane bytes", os.path.getsize(path))
    shutil.copy(path, os.path.join(out, NAME))
    shutil.rmtree(os.path.join(out, "plugins"))
    from jax.profiler import ProfileData
    data = ProfileData.from_file(os.path.join(out, NAME))
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            show = evs[:12] if plane.name.startswith("/device") else \
                [e for e in evs if e.name.startswith(("bench:", "dstpu:"))][:14]
            for e in show:
                print("     ", e.name[:60], e.start_ns, e.duration_ns,
                      {k: str(v)[:120] for k, v in e.stats})


if __name__ == "__main__":
    main()
