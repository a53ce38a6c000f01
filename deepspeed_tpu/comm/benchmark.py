"""Collective micro-benchmark — `dstpu_bench`.

Reference parity: ``bin/ds_bench`` → ``benchmarks/communication`` (all_reduce/
all_gather/all_to_all/pt2pt sweeps with bus-bandwidth reporting). TPU-first:
collectives are jit-compiled ``shard_map`` programs over the current mesh;
the sweep reports algorithmic bus bandwidth using the standard ring-collective
factors (all_reduce moves 2(n-1)/n bytes per byte of payload, all_gather and
reduce_scatter (n-1)/n, all_to_all (n-1)/n).
"""

from __future__ import annotations

import functools
import json
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from .comm import shard_map

_FACTORS = {
    "all_reduce": lambda n: 2 * (n - 1) / n,
    "all_gather": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
}


def _op_fn(op: str, axis: str):
    if op == "all_reduce":
        return lambda x: lax.psum(x, axis)
    if op == "all_gather":
        return lambda x: lax.all_gather(x, axis, tiled=True)
    if op == "reduce_scatter":
        return lambda x: lax.psum_scatter(x, axis, tiled=True)
    if op == "all_to_all":
        return lambda x: lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                        tiled=True)
    raise ValueError(f"unknown op {op}")


def bench_collective(op: str, nbytes: int, *, axis: str = "data",
                     mesh: Optional[Mesh] = None, trials: int = 10,
                     warmup: int = 2, dtype=jnp.bfloat16) -> Dict:
    """Time one collective at one payload size → result dict."""
    if mesh is None:
        devs = jax.devices()
        mesh = Mesh(np.asarray(devs).reshape(len(devs)), (axis,))
    n = mesh.shape[axis]
    elems = max(n, nbytes // jnp.dtype(dtype).itemsize)
    elems -= elems % n  # divisibility for scatter/a2a
    x = jnp.zeros((elems,), dtype)

    fn = _op_fn(op, axis)
    # out_specs is P(axis) for every op: for all_gather the per-shard output
    # is the full gathered array, so the stitched global shape is labeled
    # n× too large — harmless here, we only time the collective
    run = jax.jit(shard_map(fn, mesh=mesh, in_specs=P(axis), out_specs=P(axis)))
    r = run(x)  # compile
    for _ in range(warmup):
        r = run(x)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(trials):
        r = run(x)
    jax.block_until_ready(r)
    dt = (time.perf_counter() - t0) / trials
    payload = elems * jnp.dtype(dtype).itemsize
    busbw = payload * _FACTORS[op](n) / dt
    return {"op": op, "bytes": int(payload), "world": int(n),
            "latency_us": round(dt * 1e6, 1),
            # six places: a 4 KB collective on a loaded host is under
            # 0.0005 GB/s, and a rate never reads 0
            "algbw_GBps": round(payload / dt / 1e9, 6),
            "busbw_GBps": round(busbw / 1e9, 6)}


def sweep(ops: List[str] = ("all_reduce", "all_gather", "reduce_scatter",
                            "all_to_all"),
          sizes: List[int] = (1 << 10, 1 << 16, 1 << 20, 1 << 24),
          **kw) -> List[Dict]:
    return [bench_collective(op, size, **kw) for op in ops for size in sizes]


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="dstpu_bench",
                                description="collective bandwidth sweep")
    p.add_argument("--ops", default="all_reduce,all_gather,reduce_scatter,"
                   "all_to_all")
    p.add_argument("--maxsize", type=int, default=24,
                   help="log2 of the largest payload (default 16MB)")
    p.add_argument("--trials", type=int, default=10)
    args = p.parse_args(argv)
    sizes = [1 << b for b in range(10, args.maxsize + 1, 2)]
    for r in sweep(args.ops.split(","), sizes, trials=args.trials):
        print(json.dumps(r))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
