"""Launcher + elasticity + env-report tests (reference model:
``tests/unit/launcher``, ``tests/unit/elasticity``)."""

import subprocess
import sys

import pytest

from deepspeed_tpu.elasticity import (ElasticityError, compute_elastic_config,
                                      get_compatible_chip_counts)
from deepspeed_tpu.env_report import collect
from deepspeed_tpu.launcher.runner import (LocalRunner, PDSHRunner,
                                           build_commands, decode_world_info,
                                           encode_world_info,
                                           parse_hostfile,
                                           parse_inclusion_exclusion,
                                           parse_args)


def test_parse_hostfile():
    hosts = parse_hostfile("""
    # comment
    worker-0 slots=4
    worker-1 slots=8   # trailing
    worker-2
    """)
    assert hosts == {"worker-0": 4, "worker-1": 8, "worker-2": 1}
    with pytest.raises(ValueError):
        parse_hostfile("a slots=2\na slots=4")


def test_include_exclude_filters():
    hosts = {"w0": 4, "w1": 4, "w2": 4}
    assert parse_inclusion_exclusion(hosts, "w0@w2", "") == {"w0": 4, "w2": 4}
    assert parse_inclusion_exclusion(hosts, "", "w1") == {"w0": 4, "w2": 4}
    assert parse_inclusion_exclusion(hosts, "w0:0,1", "") == {"w0": 2}
    with pytest.raises(ValueError):
        parse_inclusion_exclusion(hosts, "w0", "w1")
    with pytest.raises(ValueError):
        parse_inclusion_exclusion(hosts, "nope", "")


def test_world_info_roundtrip():
    hosts = {"a": 4, "b": 8}
    assert decode_world_info(encode_world_info(hosts)) == hosts


def test_local_runner_cmds(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("localhost slots=1\n")
    args = parse_args(["-H", str(hf), "train.py", "--lr", "0.1"])
    runner, cmds = build_commands(args)
    assert isinstance(runner, LocalRunner)
    assert cmds == [[sys.executable, "train.py", "--lr", "0.1"]]


_POISON_JAX = """
import sys
class Poison:
    def find_spec(self, name, path=None, target=None):
        if name.split(".")[0] in ("jax", "jaxlib"):
            raise ImportError("the launcher must not import " + name)
sys.meta_path.insert(0, Poison())
"""


def test_launcher_builds_commands_without_importing_jax():
    """A parent that touched JAX holds the chip, and the processes it starts
    then fail or hang — so the launcher counts nothing through JAX: its
    command builder works in an interpreter where importing jax raises."""
    code = _POISON_JAX + """
from deepspeed_tpu.launcher.runner import build_commands, parse_args
runner, cmds = build_commands(parse_args(["-H", "/nonexistent", "train.py"]))
assert runner.name == "local" and runner.world_info == {"localhost": 1}
assert cmds == [[sys.executable, "train.py"]], cmds
assert "jax" not in sys.modules
print("ok")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60)
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-2000:]


def test_local_multi_runner_refuses_to_share_the_chips(tmp_path, monkeypatch):
    """N processes on one host would all open the same chips, and a chip
    belongs to one process at a time: outside the CPU simulation
    (``JAX_PLATFORMS=cpu``) the runner refuses to start."""
    hf = tmp_path / "hostfile"
    hf.write_text("localhost slots=1\n")
    args = parse_args(["-H", str(hf), "--num_local_procs", "4", "train.py"])
    assert len(build_commands(args)[1]) == 4  # conftest pins the CPU
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="one process at a time"):
        build_commands(args)


def test_autotuning_parent_never_initializes_a_backend(tmp_path):
    """Subprocess trials each need the chip, so the tuning parent asks a
    short-lived child for the device facts and never touches JAX itself."""
    code = """
import json, subprocess, sys
real_run = subprocess.run
def fake_run(cmd, **kw):
    assert "deepspeed_tpu.autotuning.trial_worker" in cmd, cmd
    out = {"n_chips": 4, "hbm_bytes": 1 << 34} if "--describe-devices" in cmd \\
        else {"samples_per_sec": 10.0 / json.load(open(cmd[-1]))[
            "trial_config"]["train_micro_batch_size_per_gpu"],
              "step_time_s": 0.1}
    return subprocess.CompletedProcess(cmd, 0, json.dumps(out) + "\\n", "")
subprocess.run = fake_run
from deepspeed_tpu.autotuning.cli import autotune_main
assert autotune_main(sys.argv[1]) == 0
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized(), "the parent touched JAX"
"""
    job = {"model": {"family": "llama", "config": {}},
           "config": {"train_batch_size": 8}, "tuner": "gridsearch",
           "micro_batches": [1, 2], "zero_stages": [1],
           "output": str(tmp_path / "best.json")}
    job_path = tmp_path / "job.json"
    job_path.write_text(__import__("json").dumps(job))
    r = subprocess.run([sys.executable, "-c", code, str(job_path)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    best = __import__("json").loads(r.stdout.strip().splitlines()[-1])
    assert best["best"]["micro_batch"] == 1  # dp=4 came from the child


def test_pdsh_runner_cmds(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("w0 slots=4\nw1 slots=4\n")
    args = parse_args(["-H", str(hf), "--launcher", "pdsh", "train.py"])
    # build the command lines directly (ssh may be absent in the image)
    runner = PDSHRunner(args, parse_hostfile(hf.read_text()))
    cmds = runner.get_cmd()
    assert len(cmds) == 2
    assert cmds[0][0] == "ssh" and "DSTPU_PROCESS_ID=0" in cmds[0][-1]
    assert "DSTPU_PROCESS_ID=1" in cmds[1][-1]
    assert "DSTPU_COORDINATOR=w0:8476" in cmds[1][-1]


def test_elastic_config_v02():
    ec = {"enabled": True, "max_train_batch_size": 10000,
          "micro_batch_sizes": [8, 12, 16, 17], "min_gpus": 32,
          "max_gpus": 1500, "prefer_larger_batch": True}
    batch, cfg = compute_elastic_config(ec)
    assert batch <= 10000
    assert len(cfg.compatible_chip_counts) > 1
    # effective batch identical at a specific scale
    batch2, mb, cfg2 = compute_elastic_config(ec, target_chips=64,
                                              return_microbatch=True)
    assert batch2 == batch
    assert mb * cfg2.gradient_accumulation_steps * 64 == batch


def test_elastic_default_target_consistent_with_explicit():
    """Regression: no-target selection must agree with target_chips= at the
    same scale (micro-batch preference must not flip)."""
    ec = {"enabled": True, "max_train_batch_size": 512,
          "micro_batch_sizes": [4, 8], "min_gpus": 2, "max_gpus": 16,
          "prefer_larger_batch": True}
    batch, cfg = compute_elastic_config(ec)
    batch2, mb2, cfg2 = compute_elastic_config(ec, target_chips=cfg.chips,
                                               return_microbatch=True)
    assert (batch, cfg.micro_batch_size, cfg.gradient_accumulation_steps) == \
        (batch2, mb2, cfg2.gradient_accumulation_steps)


def test_elastic_config_errors():
    with pytest.raises(ElasticityError):
        compute_elastic_config({"enabled": False})
    with pytest.raises(ElasticityError):
        compute_elastic_config({"enabled": True, "max_train_batch_size": 4,
                                "micro_batch_sizes": [0], "version": 0.2})
    ec = {"enabled": True, "max_train_batch_size": 64,
          "micro_batch_sizes": [8], "min_gpus": 1, "max_gpus": 8}
    with pytest.raises(ElasticityError):
        compute_elastic_config(ec, target_chips=7)


def test_infeasible_inputs_raise_named_elasticity_error():
    """Satellite: max_train_batch_size below the smallest micro-batch used
    to return an empty table with no diagnostic — it must raise the
    documented ElasticityError naming the infeasible inputs."""
    with pytest.raises(ElasticityError) as ei:
        get_compatible_chip_counts([8, 16], max_batch=4)
    msg = str(ei.value)
    assert "max_train_batch_size=4" in msg and "8" in msg
    # chip bounds that admit no split are named too
    with pytest.raises(ElasticityError) as ei:
        get_compatible_chip_counts([3], max_batch=3, min_chips=2,
                                   max_chips=2)
    assert "chip bounds" in str(ei.value)
    # and the config-level entry point propagates the diagnostic
    with pytest.raises(ElasticityError):
        compute_elastic_config({"enabled": True, "max_train_batch_size": 2,
                                "micro_batch_sizes": [4]})


def test_prefer_larger_micro_batch_tie_breaking():
    """Satellite: at a fixed (batch, chips) with several feasible micro
    batches, prefer_larger_batch picks the LARGEST micro batch (fewer GAS
    steps) and prefer_larger_batch=false the smallest."""
    ec = {"enabled": True, "max_train_batch_size": 8,
          "micro_batch_sizes": [1, 2], "min_gpus": 1, "max_gpus": 8}
    batch, mb, cfg = compute_elastic_config(
        dict(ec, prefer_larger_batch=True), target_chips=4,
        return_microbatch=True)
    assert (batch, mb, cfg.gradient_accumulation_steps) == (8, 2, 1)
    batch, mb, cfg = compute_elastic_config(
        dict(ec, prefer_larger_batch=False), target_chips=4,
        return_microbatch=True)
    assert (batch, mb, cfg.gradient_accumulation_steps) == (8, 1, 2)
    # the raw table is ordered the same way: first triple per chip count
    # respects the preference
    table = get_compatible_chip_counts([1, 2], 8, prefer_larger=True)
    first = [t for t in table[8] if t[0] == 4][0]
    assert first == (4, 2, 1)
    table = get_compatible_chip_counts([1, 2], 8, prefer_larger=False)
    first = [t for t in table[8] if t[0] == 4][0]
    assert first == (4, 1, 2)


def test_compatible_chip_counts_exact_batch():
    table = get_compatible_chip_counts([2, 4], max_batch=16, min_chips=1,
                                       max_chips=8)
    assert all(chips * mb * gas == b
               for b, triples in table.items()
               for chips, mb, gas in triples)


def test_env_report_collect():
    r = collect()
    assert r["backend"] == "cpu"
    assert len(r["devices"]) == 8
    assert "attention" in r["ops"]


def test_ds_report_cli_runs():
    out = subprocess.run([sys.executable, "-m", "deepspeed_tpu.env_report"],
                         capture_output=True, text=True, timeout=120,
                         env={"PATH": "/usr/bin:/bin", "HOME": "/root",
                              "JAX_PLATFORMS": "cpu",
                              "PYTHONPATH": "/root/repo"})
    assert out.returncode == 0, out.stderr
    assert "deepspeed_tpu environment report" in out.stdout


def test_mpi_family_runner_cmds(tmp_path):
    """MPI-family runners (reference OpenMPI/MPICH/IMPI/MVAPICH
    MultiNodeRunner): one launch command, rank sourced from the transport's
    own env var (exported by name via DSTPU_RANK_ENV)."""
    from deepspeed_tpu.launcher.runner import (IMPIRunner, MPICHRunner,
                                               MVAPICHRunner, OpenMPIRunner)

    hf = tmp_path / "hostfile"
    hf.write_text("w0 slots=4\nw1 slots=4\n")
    hosts = parse_hostfile(hf.read_text())

    args = parse_args(["-H", str(hf), "--launcher", "openmpi", "train.py"])
    (cmd,) = OpenMPIRunner(args, hosts).get_cmd()
    assert cmd[:3] == ["mpirun", "-np", "2"]
    assert "DSTPU_RANK_ENV=OMPI_COMM_WORLD_RANK" in cmd
    assert not any("DSTPU_PROCESS_ID" in c for c in cmd)
    assert cmd[-1] == "train.py"

    (cmd,) = MPICHRunner(args, hosts).get_cmd()
    assert cmd[:3] == ["mpiexec", "-np", "2"]
    i = cmd.index("DSTPU_RANK_ENV")
    assert cmd[i - 1] == "-genv" and cmd[i + 1] == "PMI_RANK"

    (cmd,) = IMPIRunner(args, hosts).get_cmd()
    assert cmd[0] == "mpiexec"  # hydra flags shared with MPICH

    (cmd,) = MVAPICHRunner(args, hosts).get_cmd()
    assert cmd[:3] == ["mpirun_rsh", "-np", "2"]
    assert cmd[3:5] == ["w0", "w1"]
    assert "DSTPU_RANK_ENV=MV2_COMM_WORLD_RANK" in cmd


def test_rank_env_fallback(monkeypatch):
    """comm.resolve_process_id (used by init_distributed) reads the transport
    rank var named by DSTPU_RANK_ENV when DSTPU_PROCESS_ID is absent, with
    SLURM_PROCID as final fallback."""
    from deepspeed_tpu.comm.comm import resolve_process_id

    monkeypatch.delenv("DSTPU_PROCESS_ID", raising=False)
    monkeypatch.delenv("SLURM_PROCID", raising=False)
    monkeypatch.setenv("DSTPU_RANK_ENV", "OMPI_COMM_WORLD_RANK")
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "3")
    assert resolve_process_id() == 3
    monkeypatch.setenv("DSTPU_PROCESS_ID", "1")  # launcher env wins
    assert resolve_process_id() == 1
    monkeypatch.delenv("DSTPU_PROCESS_ID")
    monkeypatch.delenv("OMPI_COMM_WORLD_RANK")
    monkeypatch.delenv("DSTPU_RANK_ENV")
    monkeypatch.setenv("SLURM_PROCID", "2")
    assert resolve_process_id() == 2
