"""Worker for test_multiprocess.py: one OS process of an n-process
data-parallel training job, bootstrapped exactly the way `bin/dstpu` does it
(DSTPU_* env → comm.init_distributed → jax.distributed.initialize).

``run()`` is the shared scenario body — _launcher_worker.py reuses it with
env-only bootstrap so the hand-spawned and launcher-spawned tests always
validate the identical workload."""

import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")


def _say(line: str) -> None:
    """One line in ONE write: the launcher's ranks share its stdout, and
    ``print`` writes the text and the newline apart where Python runs
    unbuffered (``PYTHONUNBUFFERED``), so two ranks' lines ran together."""
    sys.stdout.write(line + "\n")
    sys.stdout.flush()


def run(pid: int, n: int, tp: int = 1, mode: str = "train"):
    """Build the engine from the ambient DSTPU_* env and train 5 fixed
    steps, printing one `LOSSES {pid}/{n} ...` line."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import numpy as np

    import jax.numpy as jnp

    import deepspeed_tpu as dst
    from deepspeed_tpu.models import llama

    config = {
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 2}}
    if tp > 1:
        config["mesh"] = {"data": n, "tensor": tp}
    spec = llama.model_spec(llama.LlamaConfig.tiny(use_pipeline=False),
                            compute_dtype=jnp.float32)
    eng, *_ = dst.initialize(model=spec, config=config)
    assert jax.process_count() == n
    assert len(jax.devices()) == n * tp
    from deepspeed_tpu.comm import comm as dist
    objs = dist.all_gather_object({"rank": pid, "tag": f"w{pid}"})
    assert [o["rank"] for o in objs] == list(range(n)), objs
    rng = np.random.default_rng(0)  # same seed → same global batch everywhere
    fixed = {"tokens": rng.integers(0, 256, (8, 33), dtype=np.int32)}
    if mode == "preempt":
        return preempt_mode(eng, fixed, pid)
    losses = [float(eng.train_batch(fixed).loss) for _ in range(5)]
    _say(f"LOSSES {pid}/{n} {' '.join(f'{l:.6f}' for l in losses)}")
    assert losses[-1] < losses[0] - 1.0, losses


def main():
    pid, n, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    tp = int(sys.argv[4]) if len(sys.argv) > 4 else 1
    mode = sys.argv[5] if len(sys.argv) > 5 else "train"
    if tp > 1:
        # pod topology: several devices per process (the host's chips over
        # ICI) × several processes (DCN) — TP inside, DP across
        jax.config.update("jax_num_cpu_devices", tp)
    os.environ["DSTPU_COORDINATOR"] = f"127.0.0.1:{port}"
    os.environ["DSTPU_NUM_PROCESSES"] = str(n)
    os.environ["DSTPU_PROCESS_ID"] = str(pid)
    run(pid, n, tp, mode)


def preempt_mode(eng, fixed, pid):
    """Cross-host preemption coordination: the preemption signal (SIGUSR1
    standing in for the resource manager's SIGTERM) lands ONLY on rank 1,
    but both ranks must agree (allgather-OR) and enter the collective
    checkpoint at the SAME step."""
    import signal

    from deepspeed_tpu.elasticity.elastic_agent import PreemptionGuard

    guard = PreemptionGuard(os.environ["DSTPU_TEST_CKPT"],
                            signals=(signal.SIGUSR1,))
    for i in range(20):
        eng.train_batch(fixed)
        if pid == 1 and i == 2:  # the resource manager preempts rank 1 only
            os.kill(os.getpid(), signal.SIGUSR1)
        if guard.step_boundary(eng):
            _say(f"PREEMPTED {pid} at_boundary {i}")
            return
    raise SystemExit(f"rank {pid} never observed the peer preemption")


if __name__ == "__main__":
    main()
