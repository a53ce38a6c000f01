"""Collectives API with telemetry — capability parity with ``deepspeed/comm``.

The reference exposes a ``torch.distributed``-mirror (``comm/comm.py:223-680``:
all_reduce / all_gather / reduce_scatter / all_to_all / broadcast / barrier /
send / recv, each wrapped by ``timed_op`` for logging) backed by NCCL.

On TPU there is no runtime RPC layer: collectives are *traced* ops compiled by
XLA onto ICI/DCN. This module therefore provides:

- traced collectives over named mesh axes (``lax.psum`` etc.) for use inside
  ``shard_map``/``jit`` — with a byte/op telemetry recorder that observes them
  at trace time (the comms-logger parity, see ``utils/comms_logging.py`` in the
  reference);
- host-level helpers (``init_distributed``, ``barrier``, ``broadcast_host``)
  for the small amount of genuinely-runtime coordination (bootstrap, ckpt
  rendezvous), built on ``jax.distributed`` + ``jax.experimental.multihost_utils``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils.logging import log_dist, logger

AxisName = Union[str, Sequence[str]]


# --------------------------------------------------------------------------- #
# telemetry (comms-logger parity)
# --------------------------------------------------------------------------- #
def _tree_bytes(x: Any) -> tuple:
    """Total payload bytes + element count + representative shape(s) for an
    arbitrary pytree (arrays, scalars, dicts/lists of either). Leaves that
    carry no countable payload (strings, None) contribute zero instead of
    poisoning the total. Element count feeds the default fp32-equivalent
    accounting (what the payload would weigh uncompressed at fp32)."""
    total = 0
    elems = 0
    shapes = []
    for leaf in jax.tree_util.tree_leaves(x):
        try:
            shp = tuple(np.shape(leaf))
            n = int(np.prod(shp, dtype=np.int64))
            total += n * jnp.result_type(leaf).itemsize
            elems += n
            shapes.append(shp)
        except Exception:
            continue
    shape = shapes[0] if len(shapes) == 1 else tuple(shapes)
    return total, elems, shape


def _axis_world(axis: AxisName) -> int:
    """Members of the axis (product over tuple axes); 0 when unknown. Reads
    the installed global mesh only — never creates one as a side effect."""
    names = axis if isinstance(axis, (tuple, list)) else (axis,)
    try:
        from . import mesh as _mesh_mod

        mm = _mesh_mod._global_mesh
        if mm is None:
            return 0
        return int(np.prod([mm.axis_size(a) for a in names]))
    except Exception:
        return 0


# busbw convention (NCCL-style): wire bytes per member as a function of the
# payload and the axis world size n. Keyed by op-name prefix.
_ALGO_FACTORS = (
    ("all_reduce", lambda b, n: 2.0 * b * (n - 1) / n),
    ("inference_all_reduce", lambda b, n: 2.0 * b * (n - 1) / n),
    ("all_gather", lambda b, n: float(b) * (n - 1)),
    ("reduce_scatter", lambda b, n: b * (n - 1) / n),
    ("all_to_all", lambda b, n: b * (n - 1) / n),
    ("gather", lambda b, n: float(b) * (n - 1)),
)


def _algo_bytes(op: str, nbytes: int, world: int) -> float:
    """Estimated algorithmic ("bus") bytes a member puts on the wire."""
    if world == 1:
        return 0.0
    if world <= 0:  # axis size unknown at record time — report the payload
        return float(nbytes)
    for prefix, f in _ALGO_FACTORS:
        if op.startswith(prefix):
            return f(nbytes, world)
    return float(nbytes)  # broadcast / ppermute / send_recv / scatter


def _link_class(axis: AxisName) -> str:
    """Classify the slowest link tier a collective over ``axis`` crosses:
    ``"dcn"`` when any named axis is in the installed mesh's ``dcn_axes``
    (the cross-island tier — multi-slice DCN, or the 2-level ``data`` axis
    of an hpZ/MiCS carve) with size > 1, else ``"ici"``. Unknown mesh →
    ``"ici"`` (single-tier)."""
    names = axis if isinstance(axis, (tuple, list)) else (axis,)
    try:
        from . import mesh as _mesh_mod

        mm = _mesh_mod._global_mesh
        if mm is None:
            return "ici"
        dcn = tuple(getattr(mm, "dcn_axes", ()) or ())
        for a in names:
            if a in dcn and mm.axis_size(a) > 1:
                return "dcn"
    except Exception:
        pass
    return "ici"


def _trace_site() -> str:
    """Nearest stack frame outside this module — where the collective was
    issued from (the reference comms logger's caller_func analog)."""
    import traceback

    this = os.path.abspath(__file__)
    for fr in reversed(traceback.extract_stack()):
        if os.path.abspath(fr.filename) != this:
            return f"{os.path.basename(fr.filename)}:{fr.lineno}"
    return "?"


@dataclass
class CommsTelemetry:
    """Records every traced collective: op name, axis, payload bytes,
    trace-site, and estimated algorithmic (bus) bytes. Since collectives are
    compile-time constructs, records are per-trace (not per-step) — one entry
    describes what every execution of the compiled step does. Byte accounting
    is pytree-aware: payloads may be arrays, scalars, or nested containers.

    ``repeats`` covers collectives traced once but executed several times per
    step (a ``lax.scan`` body over GAS micro-batches): the record carries the
    per-execution payload and the summary multiplies count/bytes by
    ``repeats``, so per-step volume comparisons (per-micro vs deferred
    reduction) stay honest.

    ``prof_all``/``prof_ops`` mirror the reference comms-logger config
    (``utils/comms_logging.py``): with ``prof_all`` off, only ops whose name
    starts with an entry of ``prof_ops`` are recorded."""

    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = field(default_factory=list)
    records: List[Dict[str, Any]] = field(default_factory=list)
    ring_stats: Dict[str, float] = field(default_factory=dict)

    def _profiled(self, op: str) -> bool:
        if self.prof_all:
            return True
        return any(op == p or op.startswith(p) for p in self.prof_ops)

    def record(self, op: str, axis: AxisName, x: Any,
               repeats: int = 1, fp32_equiv: Optional[float] = None) -> None:
        """``fp32_equiv``: bytes the payload would weigh uncompressed at
        fp32. Defaults to element-count × 4; quantized collectives pass the
        SOURCE element count explicitly (their int8+scales payload carries
        more elements than the fp32 tensor it encodes), so the per-op
        compression ratio fp32_equiv/bytes stays honest."""
        if not self.enabled or not self._profiled(op):
            return
        nbytes, elems, shape = _tree_bytes(x)
        world = _axis_world(axis)
        rec = {"op": op, "axis": axis, "bytes": nbytes, "shape": shape,
               "world": world, "algo_bytes": _algo_bytes(op, nbytes, world),
               "repeats": max(int(repeats), 1), "site": _trace_site(),
               "link": _link_class(axis),
               "fp32_equiv_bytes": (float(fp32_equiv)
                                    if fp32_equiv is not None
                                    else float(elems * 4))}
        self.records.append(rec)
        if self.verbose:
            logger.info(f"comm: {op} over {axis}: {nbytes} bytes "
                        f"{rec['shape']} from {rec['site']}")

    def record_ring(self, key: str, value: float,
                    accumulate: bool = True) -> None:
        """Ring-attention series (``Comm/ring/<key>`` — the closed
        ``telemetry.schema.COMM_RING_SERIES`` registry): trace-time
        hop/byte counters from ``sequence.ring``, the host-measured
        ``overlap_frac`` gauge, and the dense-fallback marker. Unlike
        ``record`` this is NOT gated on ``enabled`` — the dense-fallback
        marker must surface even when the comms logger is off."""
        v = float(value)
        if accumulate:
            self.ring_stats[key] = self.ring_stats.get(key, 0.0) + v
        else:
            self.ring_stats[key] = v

    def summary(self) -> Dict[str, Dict[str, Any]]:
        out: Dict[str, Dict[str, Any]] = {}
        for r in self.records:
            s = out.setdefault(r["op"], {"count": 0, "bytes": 0,
                                         "algo_bytes": 0.0,
                                         "algo_bytes_dcn": 0.0,
                                         "algo_bytes_ici": 0.0,
                                         "fp32_equiv_bytes": 0.0,
                                         "sites": []})
            rep = max(int(r.get("repeats", 1)), 1)
            s["count"] += rep
            s["bytes"] += max(r["bytes"], 0) * rep
            algo = max(r.get("algo_bytes", 0.0), 0.0) * rep
            s["algo_bytes"] += algo
            s["algo_bytes_" + r.get("link", "ici")] += algo
            s["fp32_equiv_bytes"] += \
                max(r.get("fp32_equiv_bytes", 0.0), 0.0) * rep
            site = r.get("site")
            if site and site not in s["sites"]:
                s["sites"].append(site)
        return out

    def total_algo_bytes(self, link: Optional[str] = None) -> float:
        """Per-step algorithmic bytes across every recorded collective;
        ``link`` = "dcn" | "ici" restricts to that link class."""
        key = "algo_bytes" if link is None else f"algo_bytes_{link}"
        return sum(s[key] for s in self.summary().values())

    def log_summary(self, step_time_s: Optional[float] = None) -> None:
        """Periodic per-op rollup (reference ``log_summary()``); with a step
        time, adds the estimated algorithmic bandwidth of the compiled step."""
        for op, s in sorted(self.summary().items()):
            msg = (f"comm summary | {op}: count={s['count']} "
                   f"bytes={s['bytes']:,} algo_bytes={s['algo_bytes']:,.0f}")
            if step_time_s:
                msg += f" busbw~{s['algo_bytes'] / step_time_s / 1e9:.2f} GB/s"
            if s["sites"]:
                msg += f" sites={','.join(s['sites'][:4])}"
            logger.info(msg)

    def events(self, step: int) -> List[tuple]:
        """Monitor events (``Comm/<op>/{bytes,count,algo_bytes,
        algo_bytes_dcn,algo_bytes_ici,fp32_equiv_bytes}``) for the current
        trace records — cumulative per trace, constant across executed
        steps. The metric suffixes form the closed ``telemetry.schema.
        COMM_METRICS`` registry; a new suffix here must be registered
        there."""
        ev = []
        for op, s in sorted(self.summary().items()):
            ev.append((f"Comm/{op}/bytes", float(s["bytes"]), step))
            ev.append((f"Comm/{op}/count", float(s["count"]), step))
            ev.append((f"Comm/{op}/algo_bytes", float(s["algo_bytes"]), step))
            ev.append((f"Comm/{op}/algo_bytes_dcn",
                       float(s["algo_bytes_dcn"]), step))
            ev.append((f"Comm/{op}/algo_bytes_ici",
                       float(s["algo_bytes_ici"]), step))
            ev.append((f"Comm/{op}/fp32_equiv_bytes",
                       float(s["fp32_equiv_bytes"]), step))
        for key, val in sorted(self.ring_stats.items()):
            ev.append((f"Comm/ring/{key}", float(val), step))
        return ev

    def reset(self) -> None:
        self.records.clear()
        self.ring_stats.clear()


_telemetry = CommsTelemetry()


def get_telemetry() -> CommsTelemetry:
    return _telemetry


def configure(enabled: bool = False, verbose: bool = False,
              prof_all: bool = True, prof_ops: Optional[List[str]] = None,
              debug: bool = False) -> None:
    """Reference parity: ``dist.configure(config)`` enabling the comms logger."""
    _telemetry.enabled = enabled
    _telemetry.verbose = verbose
    _telemetry.prof_all = prof_all
    _telemetry.prof_ops = list(prof_ops or [])
    _telemetry.debug = debug


# --------------------------------------------------------------------------- #
# shard_map
# --------------------------------------------------------------------------- #
def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map`` with this package's defaults (no replication check;
    ``axis_names`` as any iterable). Every manual collective region in the
    framework goes through here."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


# --------------------------------------------------------------------------- #
# traced collectives (use inside shard_map / jit with named axes)
# --------------------------------------------------------------------------- #
def all_reduce(x, axis: AxisName, op: str = "sum"):
    """psum/pmax/pmin/pmean over a mesh axis (reference ``dist.all_reduce``)."""
    _telemetry.record(f"all_reduce_{op}", axis, x)
    if op == "sum":
        return lax.psum(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    if op in ("mean", "avg"):
        return lax.pmean(x, axis)
    raise ValueError(f"unsupported reduce op {op}")


def all_gather(x, axis: AxisName, *, tiled: bool = True, gather_axis: int = 0):
    """Gather shards along ``gather_axis`` (reference ``dist.all_gather_into_tensor``)."""
    _telemetry.record("all_gather", axis, x)
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: AxisName, *, scatter_axis: int = 0, op: str = "sum"):
    """Sum-reduce then scatter along ``scatter_axis`` (reference
    ``dist.reduce_scatter_tensor``)."""
    _telemetry.record("reduce_scatter", axis, x)
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)


def all_to_all(x, axis: AxisName, *, split_axis: int, concat_axis: int):
    """Ulysses-style all-to-all (reference ``dist.all_to_all_single``,
    ``sequence/layer.py single_all_to_all``)."""
    _telemetry.record("all_to_all", axis, x)
    return lax.all_to_all(x, axis, split_axis=split_axis, concat_axis=concat_axis,
                          tiled=True)


def ppermute(x, axis: AxisName, perm: Sequence[tuple]):
    """Point-to-point ring shift — the TPU replacement for the reference's
    ``runtime/pipe/p2p.py`` send/recv pairs."""
    _telemetry.record("ppermute", axis, x)
    return lax.ppermute(x, axis, perm=perm)


def ring_shift(x, axis: str, axis_size: int, shift: int = 1):
    """Shift shards around the ring by ``shift`` (ring attention building block)."""
    perm = [(i, (i + shift) % axis_size) for i in range(axis_size)]
    return ppermute(x, axis, perm)


def broadcast(x, axis: AxisName, src_index: int = 0):
    """Broadcast the ``src_index`` shard to all members of the axis."""
    _telemetry.record("broadcast", axis, x)
    full = lax.all_gather(x, axis, axis=0, tiled=False)
    return full[src_index]


def send_recv(x, axis: AxisName, src: int, dst: int):
    """Single point-to-point transfer (reference ``dist.send/recv``): every
    member passes its value; the ``dst`` member receives ``src``'s value,
    all others receive zeros (collective semantics of p2p under SPMD)."""
    _telemetry.record("send_recv", axis, x)
    return lax.ppermute(x, axis, perm=[(src, dst)])


def gather(x, axis: AxisName, dst: int = 0):
    """Gather all shards to the ``dst`` member, zeros elsewhere (reference
    ``dist.gather``). Under SPMD every member computes the gather; masking
    keeps only the root's copy live so XLA can DCE the rest."""
    _telemetry.record("gather", axis, x)
    full = lax.all_gather(x, axis, axis=0, tiled=False)
    keep = lax.axis_index(axis) == dst
    return jnp.where(keep, full, jnp.zeros_like(full))


def scatter(x, axis: AxisName, src: int = 0):
    """Scatter the ``src`` member's leading-dim chunks over the axis
    (reference ``dist.scatter``). x: [axis_size, ...] on src."""
    _telemetry.record("scatter", axis, x)
    from_src = broadcast(x, axis, src_index=src)
    return from_src[lax.axis_index(axis)]


def inference_all_reduce(x, axis: AxisName = "tensor"):
    """TP-forward allreduce (reference ``dist.inference_all_reduce`` — same
    wire op, separate name so comm logs can distinguish serving traffic)."""
    _telemetry.record("inference_all_reduce", axis, x)
    return lax.psum(x, axis)


def monitored_barrier(name: str = "dstpu_barrier", timeout: Optional[float] = None):
    """Reference ``dist.monitored_barrier``: a barrier that DETECTS stragglers
    — raises within ``timeout`` seconds if the barrier does not complete
    (e.g. a dead host), instead of hanging forever."""
    import threading as _threading
    import time as _time

    t0 = _time.perf_counter()
    if timeout is None:
        barrier(name)
        return _time.perf_counter() - t0
    err: list = []
    done = _threading.Event()

    def _run():
        try:
            barrier(name)
        except Exception as e:  # surfaced below
            err.append(e)
        finally:
            done.set()

    t = _threading.Thread(target=_run, daemon=True, name=f"barrier:{name}")
    t.start()
    if not done.wait(timeout):
        raise RuntimeError(f"monitored_barrier '{name}' timed out after "
                           f"{timeout}s — straggler or dead process")
    if err:
        raise err[0]
    return _time.perf_counter() - t0


def axis_index(axis: AxisName):
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    from .mesh import get_mesh

    return get_mesh().axis_size(axis)


# --------------------------------------------------------------------------- #
# host-level runtime coordination
# --------------------------------------------------------------------------- #
_initialized = False


def resolve_process_id() -> int:
    """Rank resolution for the multi-host bootstrap: launcher env first;
    then the transport's own rank var — the MPI-family runners export its
    NAME via ``DSTPU_RANK_ENV`` (OMPI_COMM_WORLD_RANK / PMI_RANK /
    MV2_COMM_WORLD_RANK) since one mpirun command line cannot carry per-rank
    ids — and SLURM rank as the final fallback (same single-command
    limitation)."""
    pid = os.environ.get("DSTPU_PROCESS_ID")
    if pid is None and (rank_env := os.environ.get("DSTPU_RANK_ENV")):
        pid = os.environ.get(rank_env)
    if pid is None:
        pid = os.environ.get("SLURM_PROCID", 0)
    return int(pid)


def init_distributed(dist_backend: str = "xla",
                     coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     auto_mpi_discovery: bool = True,
                     **kwargs) -> None:
    """Multi-host bootstrap (reference ``comm.init_distributed`` ``comm/comm.py:788``).

    On TPU pods the runtime handles rendezvous natively; ``jax.distributed
    .initialize`` is only needed for multi-process CPU/GPU or explicit
    coordinator setups. Single-process: no-op.
    """
    global _initialized
    if _initialized:
        return
    env_procs = os.environ.get("DSTPU_NUM_PROCESSES")
    if coordinator_address is None:
        coordinator_address = os.environ.get("DSTPU_COORDINATOR")
    if coordinator_address is None and env_procs is None and num_processes is None:
        _initialized = True  # single-process / TPU-native bootstrap
        log_dist("init_distributed: single-process or TPU-native rendezvous")
        return
    if not jax.distributed.is_initialized():  # else: the launcher did it
        if process_id is None:
            process_id = resolve_process_id()
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes or int(env_procs or 1),
            process_id=process_id)
    _initialized = True
    log_dist(f"init_distributed: {jax.process_count()} processes")


def is_initialized() -> bool:
    return _initialized


def get_rank() -> int:
    return jax.process_index()


def get_world_size() -> int:
    return jax.process_count()


def barrier(name: str = "dstpu_barrier") -> None:
    """Host-level barrier across processes (reference ``dist.barrier``)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def broadcast_host(value, src: int = 0):
    """Broadcast host data from one process to all (ckpt tags etc.)."""
    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(value, is_source=jax.process_index() == src)


def all_gather_object(obj):
    """Gather one picklable host object per process → list ordered by rank
    (reference ``dist.all_gather_object`` :247). Two phases: agree on the max
    pickle size, then gather fixed-width byte buffers."""
    import pickle

    import numpy as np

    if jax.process_count() == 1:
        return [obj]
    from jax.experimental import multihost_utils

    payload = np.frombuffer(pickle.dumps(obj), np.uint8)
    sizes = multihost_utils.process_allgather(
        np.asarray([payload.size], np.int64))
    width = int(sizes.max())
    padded = np.zeros((width,), np.uint8)
    padded[:payload.size] = payload
    gathered = multihost_utils.process_allgather(padded)
    return [pickle.loads(gathered[r, :int(sizes[r, 0])].tobytes())
            for r in range(jax.process_count())]


def broadcast_object_list(object_list, src: int = 0):
    """In-place broadcast of a list of picklable objects from ``src``
    (reference ``dist.broadcast_object_list`` :229). Only ``src`` pickles —
    non-src placeholders may be unpicklable, matching the torch contract —
    and the wire carries one payload, not an all-gather."""
    import pickle

    import numpy as np

    if jax.process_count() == 1:
        return object_list
    from jax.experimental import multihost_utils

    is_src = jax.process_index() == src
    payload = (np.frombuffer(pickle.dumps(list(object_list)), np.uint8)
               if is_src else np.zeros((0,), np.uint8))
    size = multihost_utils.broadcast_one_to_all(
        np.asarray([payload.size], np.int64), is_source=is_src)
    width = int(size[0])
    padded = np.zeros((width,), np.uint8)
    if is_src:
        padded[:payload.size] = payload
    data = multihost_utils.broadcast_one_to_all(padded, is_source=is_src)
    for i, obj in enumerate(pickle.loads(np.asarray(data).tobytes())):
        object_list[i] = obj
    return object_list
