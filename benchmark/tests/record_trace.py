"""Records the small trace kept beside the tests (``recorded.xplane.pb``).

Run on the chip, once, by the PR that defines the benchmark:

    chiprun --chips 1 -- python3 benchmark/tests/record_trace.py 1
    chiprun --chips 4 -- python3 benchmark/tests/record_trace.py 4

A matmul program, a Pallas kernel and (on four chips) an all-reduce under
``bench:`` host spans with a sleep between them, so that the file holds
busy time, idle gaps under known spans, a Mosaic call and a collective. It
also prints the planes, lines and a few events, which is how the reduction
in ``harness/trace.py`` was written against the real names.
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


def main(chips: int) -> None:
    out = os.path.join(ROOT, "chiprun_out", f"record_trace_{chips}")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    devs = jax.devices()
    assert len(devs) == chips, devs
    x = jnp.ones((2048, 2048), jnp.bfloat16)

    def add_kernel(a_ref, o_ref):
        o_ref[...] = a_ref[...] + 1

    @jax.jit
    def program(a):
        b = a @ a
        b = pl.pallas_call(add_kernel, out_shape=jax.ShapeDtypeStruct(
            b.shape, b.dtype))(b)
        return jax.lax.fori_loop(0, 3, lambda i, c: (c @ a) * 0.001, b)

    if chips > 1:
        mesh = jax.make_mesh((chips,), ("data",))
        sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
        xs = jax.device_put(jnp.ones((chips * 1024, 2048), jnp.bfloat16), sh)

        @jax.jit
        def sharded(a):
            w = a.T @ a            # contraction over the sharded axis
            return jax.lax.with_sharding_constraint(w @ w, sh)
        sharded(xs).block_until_ready()
    program(x).block_until_ready()
    spans = Spans()
    jax.profiler.start_trace(out)
    with spans.span("window"):
        for _ in range(3):
            with spans.span("tick"):
                program(x).block_until_ready()
                if chips > 1:
                    sharded(xs).block_until_ready()
            with spans.span("harvest"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins/profile/*/*.xplane.pb"))[0]
    print("xplane bytes", os.path.getsize(path))
    shutil.copy(path, os.path.join(out, f"recorded_{chips}.xplane.pb"))
    shutil.rmtree(os.path.join(out, "plugins"))
    from jax.profiler import ProfileData
    data = ProfileData.from_file(os.path.join(out, f"recorded_{chips}.xplane.pb"))
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            show = evs[:12] if plane.name.startswith("/device") else \
                [e for e in evs if e.name.startswith("bench:")][:6]
            for e in show:
                print("     ", e.name, e.start_ns, e.duration_ns,
                      {k: str(v)[:160] for k, v in dict(e.stats).items()})
    print("host spans", [(n, round(a, 6), round(b, 6)) for n, a, b in spans.records])


if __name__ == "__main__":
    main(int(sys.argv[1]))
