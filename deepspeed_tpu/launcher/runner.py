"""Multi-host launcher CLI.

Reference parity: ``bin/deepspeed`` → ``launcher/runner.py:436 main`` (hostfile
parse :230, --include/--exclude filters :310) → per-node ``launcher/launch.py``
and the ``MultiNodeRunner`` family (``multinode_runner.py``: PDSH/MPI/SLURM).

TPU-first redesign: the reference forks one OS process per GPU and wires NCCL
ranks; on TPU the unit is one process per HOST (each process drives all local
chips), and the only true bootstrap job is ``jax.distributed.initialize`` —
so the launcher's work is (a) resolve the host list, (b) start one process per
host with coordinator env (``DSTPU_COORDINATOR``, ``DSTPU_NUM_PROCESSES``,
``DSTPU_PROCESS_ID``), via ssh/pdsh/slurm or locally.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import shlex
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

from ..utils.logging import logger

DEFAULT_COORD_PORT = 8476


# --------------------------------------------------------------------------- #
# hostfile handling (reference runner.py:230 fetch_hostfile)
# --------------------------------------------------------------------------- #
def parse_hostfile(text: str) -> Dict[str, int]:
    """'hostname slots=N' lines → {host: slots}. Comments/#/blank ignored."""
    hosts: Dict[str, int] = {}
    for line in text.splitlines():
        line = line.split("#")[0].strip()
        if not line:
            continue
        parts = line.split()
        host = parts[0]
        slots = 1
        for p in parts[1:]:
            if p.startswith("slots="):
                slots = int(p.split("=", 1)[1])
        if host in hosts:
            raise ValueError(f"duplicate host {host} in hostfile")
        hosts[host] = slots
    return hosts


def fetch_hostfile(path: Optional[str]) -> Optional[Dict[str, int]]:
    if not path or not os.path.isfile(path):
        return None
    with open(path) as f:
        return parse_hostfile(f.read())


def parse_inclusion_exclusion(hosts: Dict[str, int], include: str,
                              exclude: str) -> Dict[str, int]:
    """'--include host1@host2' / '--exclude host3' filters (reference :310).
    Per-slot syntax 'host:0,1' limits slot count on that host."""

    def parse_filter(s: str) -> Dict[str, Optional[List[int]]]:
        out: Dict[str, Optional[List[int]]] = {}
        for item in filter(None, s.split("@")):
            if ":" in item:
                host, slots = item.split(":", 1)
                out[host] = [int(x) for x in slots.split(",")]
            else:
                out[item] = None
        return out

    inc, exc = parse_filter(include), parse_filter(exclude)
    if inc and exc:
        raise ValueError("--include and --exclude are mutually exclusive")
    result = dict(hosts)
    if inc:
        result = {}
        for host, slots in inc.items():
            if host not in hosts:
                raise ValueError(f"included host {host} not in hostfile")
            result[host] = len(slots) if slots else hosts[host]
    for host, slots in exc.items():
        if host not in result:
            raise ValueError(f"excluded host {host} not in hostfile")
        if slots is None:
            del result[host]
        else:
            result[host] = max(0, result[host] - len(slots))
    return {h: s for h, s in result.items() if s > 0}


def encode_world_info(hosts: Dict[str, int]) -> str:
    """base64 world info passed to every node (reference :401)."""
    return base64.urlsafe_b64encode(json.dumps(hosts).encode()).decode()


def decode_world_info(blob: str) -> Dict[str, int]:
    return json.loads(base64.urlsafe_b64decode(blob.encode()).decode())


# --------------------------------------------------------------------------- #
# multi-node runners (reference multinode_runner.py)
# --------------------------------------------------------------------------- #
class MultiNodeRunner:
    """Builds the per-node command lines; subclasses pick the transport."""

    name = "base"

    def __init__(self, args, world_info: Dict[str, int]):
        self.args = args
        self.world_info = world_info
        self.hosts = list(world_info.keys())

    def backend_exists(self) -> bool:
        return True

    def node_env(self, process_id: int) -> Dict[str, str]:
        coordinator = f"{self.hosts[0]}:{self.args.coordinator_port}"
        return {
            "DSTPU_COORDINATOR": coordinator,
            "DSTPU_NUM_PROCESSES": str(len(self.hosts)),
            "DSTPU_PROCESS_ID": str(process_id),
            "DSTPU_WORLD_INFO": encode_world_info(self.world_info),
        }

    def user_cmd(self) -> List[str]:
        return [sys.executable, self.args.user_script] + self.args.user_args

    def get_cmd(self) -> List[List[str]]:
        raise NotImplementedError


class LocalRunner(MultiNodeRunner):
    """Single host: exec the user script in-place with bootstrap env."""

    name = "local"

    def get_cmd(self) -> List[List[str]]:
        return [self.user_cmd()]


class LocalMultiRunner(MultiNodeRunner):
    """N processes on ONE host, coordinator on localhost — the reference's
    per-device fork (``launcher/launch.py:145`` spawns ``num_local_procs``
    workers with RANK/LOCAL_RANK env). This is the CPU simulation path: the
    same bootstrap contract (``jax.distributed.initialize``) as a real
    multi-host launch, which makes it the end-to-end launcher test double.

    It is NOT how a TPU host is driven. A chip belongs to one process at a
    time and ONE process drives all the chips of a host (four on a v5e 2x2
    host), so N processes that each open the default backend would fight
    over the same chips: the first holds them and the rest fail or hang.
    Nothing here hands a process a chip of its own, so the runner refuses
    to start unless the launch environment keeps every child off the chip
    (``JAX_PLATFORMS=cpu``)."""

    name = "local_multi"

    def __init__(self, args, world_info: Dict[str, int], nproc: int):
        super().__init__(args, world_info)
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            raise RuntimeError(
                f"--num_local_procs {nproc} would start {nproc} processes "
                f"that all open the host's chips, and a chip belongs to one "
                f"process at a time. One process drives every local chip: "
                f"drop --num_local_procs, or set JAX_PLATFORMS=cpu for the "
                f"CPU simulation this mode exists for")
        self.nproc = nproc

    def node_env(self, process_id: int) -> Dict[str, str]:
        env = super().node_env(process_id)
        env["DSTPU_COORDINATOR"] = \
            f"127.0.0.1:{self.args.coordinator_port}"
        env["DSTPU_NUM_PROCESSES"] = str(self.nproc)
        # world info must agree with the actual process count, not the
        # 1-host hostfile it was derived from
        env["DSTPU_WORLD_INFO"] = encode_world_info({"localhost": self.nproc})
        return env

    def get_cmd(self) -> List[List[str]]:
        return [self.user_cmd() for _ in range(self.nproc)]


class PDSHRunner(MultiNodeRunner):
    """ssh fan-out, one command per host (reference PDSHRunner :55 — we emit
    explicit per-host ssh lines rather than requiring pdsh)."""

    name = "pdsh"

    def backend_exists(self) -> bool:
        from shutil import which

        return which("ssh") is not None

    def get_cmd(self) -> List[List[str]]:
        cmds = []
        for pid, host in enumerate(self.hosts):
            env = self.node_env(pid)
            envs = " ".join(f"{k}={shlex.quote(v)}" for k, v in env.items())
            remote = f"cd {shlex.quote(os.getcwd())} && {envs} " + \
                " ".join(shlex.quote(c) for c in self.user_cmd())
            cmds.append(["ssh", "-o", "StrictHostKeyChecking=no", host, remote])
        return cmds


class SlurmRunner(MultiNodeRunner):
    """srun launch (reference SlurmRunner :345)."""

    name = "slurm"

    def backend_exists(self) -> bool:
        from shutil import which

        return which("srun") is not None

    def get_cmd(self) -> List[List[str]]:
        n = len(self.hosts)
        cmd = ["srun", f"--nodes={n}", "--ntasks-per-node=1",
               f"--nodelist={','.join(self.hosts)}",
               "--export=ALL," + ",".join(
                   f"{k}={v}" for k, v in self.node_env(0).items()
                   if k != "DSTPU_PROCESS_ID")]
        return [cmd + self.user_cmd()]


class OpenMPIRunner(MultiNodeRunner):
    """mpirun launch, one rank per host (reference OpenMPIRunner :126).
    Process id comes from OMPI's rank env var at bootstrap time, so the
    exported env omits DSTPU_PROCESS_ID (comm.init_distributed reads
    OMPI_COMM_WORLD_RANK as a fallback)."""

    name = "openmpi"
    launcher = "mpirun"
    rank_env = "OMPI_COMM_WORLD_RANK"

    def backend_exists(self) -> bool:
        from shutil import which

        return which(self.launcher) is not None

    def _env_flags(self) -> List[str]:
        flags: List[str] = []
        for k, v in self.node_env(0).items():
            if k == "DSTPU_PROCESS_ID":
                continue
            flags += ["-x", f"{k}={v}"]
        flags += ["-x", f"DSTPU_RANK_ENV={self.rank_env}"]
        return flags

    def get_cmd(self) -> List[List[str]]:
        n = len(self.hosts)
        cmd = [self.launcher, "-np", str(n),
               "--host", ",".join(self.hosts), "--map-by", "ppr:1:node"]
        return [cmd + self._env_flags() + self.user_cmd()]


class MPICHRunner(OpenMPIRunner):
    """mpiexec (MPICH/hydra) launch (reference MPICHRunner :188)."""

    name = "mpich"
    launcher = "mpiexec"
    rank_env = "PMI_RANK"

    def _env_flags(self) -> List[str]:
        flags: List[str] = []
        for k, v in self.node_env(0).items():
            if k == "DSTPU_PROCESS_ID":
                continue
            flags += ["-genv", k, v]
        flags += ["-genv", "DSTPU_RANK_ENV", self.rank_env]
        return flags

    def get_cmd(self) -> List[List[str]]:
        n = len(self.hosts)
        cmd = [self.launcher, "-np", str(n), "-hosts", ",".join(self.hosts),
               "-ppn", "1"]
        return [cmd + self._env_flags() + self.user_cmd()]


class IMPIRunner(MPICHRunner):
    """Intel MPI: hydra flags, PMI rank (reference IMPIRunner :260)."""

    name = "impi"


class MVAPICHRunner(MPICHRunner):
    """MVAPICH: mpirun_rsh transport, MV2 rank var (reference :393)."""

    name = "mvapich"
    launcher = "mpirun_rsh"
    rank_env = "MV2_COMM_WORLD_RANK"

    def get_cmd(self) -> List[List[str]]:
        n = len(self.hosts)
        cmd = [self.launcher, "-np", str(n)] + list(self.hosts)
        env = [f"{k}={v}" for k, v in self.node_env(0).items()
               if k != "DSTPU_PROCESS_ID"]
        env.append(f"DSTPU_RANK_ENV={self.rank_env}")
        return [cmd + env + self.user_cmd()]


RUNNERS = {"local": LocalRunner, "pdsh": PDSHRunner, "slurm": SlurmRunner,
           "openmpi": OpenMPIRunner, "mpich": MPICHRunner,
           "impi": IMPIRunner, "mvapich": MVAPICHRunner}


# --------------------------------------------------------------------------- #
def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="dstpu",
        description="deepspeed_tpu launcher: start one process per host and "
                    "bootstrap jax.distributed")
    p.add_argument("-H", "--hostfile", default="/job/hostfile")
    p.add_argument("-i", "--include", default="")
    p.add_argument("-e", "--exclude", default="")
    p.add_argument("--num_nodes", type=int, default=-1)
    p.add_argument("--launcher", default="local", choices=sorted(RUNNERS))
    p.add_argument("--num_local_procs", type=int, default=0,
                   help="spawn N coordinated processes on THIS host "
                        "(reference launch.py per-device fork; CPU "
                        "simulation / single-host multi-process)")
    p.add_argument("--coordinator_port", type=int, default=DEFAULT_COORD_PORT)
    p.add_argument("--elastic_training", action="store_true")
    p.add_argument("--min_elastic_nodes", type=int, default=-1)
    p.add_argument("--max_elastic_nodes", type=int, default=-1)
    p.add_argument("--force_multi", action="store_true")
    p.add_argument("--autotuning", choices=["tune"], default=None,
                   help="run the autotuner instead of launching: "
                        "user_script is an autotuning job JSON; trials run "
                        "in isolated worker processes and the best config "
                        "is written to the job's 'output' path (reference "
                        "deepspeed --autotuning; the reference's 'run' mode "
                        "is the same sweep + relaunch — here relaunch with "
                        "the emitted best_config yourself)")
    p.add_argument("user_script")
    p.add_argument("user_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def build_commands(args) -> Tuple[MultiNodeRunner, List[List[str]]]:
    hosts = fetch_hostfile(args.hostfile)
    if hosts is None:
        # one process drives every local chip, so localhost is ONE slot —
        # the same default a hostfile line without ``slots=`` gets. (The
        # launcher never asks JAX: a parent that touched the chip would
        # keep it from the children it starts.)
        hosts = {"localhost": 1}
    hosts = parse_inclusion_exclusion(hosts, args.include, args.exclude)
    if args.num_nodes > 0:
        hosts = dict(list(hosts.items())[:args.num_nodes])
    if args.num_local_procs > 1:
        if len(hosts) > 1:
            raise ValueError(
                "--num_local_procs is a single-host mode; restrict the "
                "hostfile with --include/--num_nodes 1")
        if args.launcher != "local":
            raise ValueError(
                f"--num_local_procs forks plain local processes and cannot "
                f"honor --launcher {args.launcher}; drop one of the two")
        runner = LocalMultiRunner(args, hosts, args.num_local_procs)
        return runner, runner.get_cmd()
    if len(hosts) > 1 and args.launcher == "local":
        # ADVICE r1: silently falling back to one local process while
        # node_env still advertises len(hosts) peers makes
        # jax.distributed.initialize hang forever waiting for the others
        raise ValueError(
            f"hostfile resolves {len(hosts)} hosts but --launcher local runs "
            f"a single process; pick --launcher ssh/slurm/mpi or restrict "
            f"with --include/--num_nodes 1")
    multi = (len(hosts) > 1 or args.force_multi) and args.launcher != "local"
    runner_cls = RUNNERS[args.launcher if multi else "local"]
    runner = runner_cls(args, hosts)
    if not runner.backend_exists():
        raise RuntimeError(f"launcher backend '{runner.name}' unavailable")
    return runner, runner.get_cmd()


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.autotuning:
        # trials self-launch as isolated worker processes; no host fan-out
        from ..autotuning.cli import autotune_main

        return autotune_main(args.user_script, args.user_args)
    runner, cmds = build_commands(args)
    logger.info(f"launching {len(cmds)} command(s) via {runner.name}")
    procs = []
    for pid, cmd in enumerate(cmds):
        env = dict(os.environ)
        if runner.name != "slurm":
            env.update(runner.node_env(pid if runner.name != "local" else 0))
        procs.append(subprocess.Popen(cmd, env=env))
    # reap as a GROUP: one worker dying (nonzero) must kill its siblings —
    # survivors would otherwise block in jax.distributed.initialize waiting
    # for the dead rank forever (reference launch.py kills the local group
    # the same way)
    rc = 0
    live = list(procs)
    try:
        while live:
            time.sleep(0.2)
            for pr in list(live):
                ret = pr.poll()
                if ret is None:
                    continue
                live.remove(pr)
                rc = ret or rc
                if ret and live:
                    logger.error(
                        f"worker pid {pr.pid} exited rc={ret}; terminating "
                        f"{len(live)} sibling(s)")
                    for sib in live:
                        sib.terminate()
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.kill()
    return rc


if __name__ == "__main__":
    sys.exit(main())
