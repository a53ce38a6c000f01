"""Driver-artifact contracts: bench.py prints one JSON line with the agreed
schema, refuses to run without a TPU unless the CPU is asked for by name,
and __graft_entry__ exposes a jittable entry."""

import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(*args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               p for p in (REPO_ROOT, os.environ.get("PYTHONPATH")) if p)}
    env.pop("XLA_FLAGS", None)  # tiny single-device run is faster
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"), *args],
        capture_output=True, text=True, timeout=1200, env=env)
    lines = [l for l in out.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, out.stdout[-500:] + out.stderr[-2000:]
    return out.returncode, json.loads(lines[0])


def test_bench_emits_schema_compliant_json():
    """The explicit CPU run: labelled ``cpu``, and every time or rate null —
    a CPU timing never appears under a device metric's name."""
    rc, rec = _run_bench("--cpu")
    assert rc == 0, rec
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in rec, rec
    assert rec["metric"] == "llama_zero3_train_mfu"
    assert rec["detail"]["ok"] is True
    assert rec["detail"]["backend"] == "cpu"
    assert rec["value"] is None and rec["vs_baseline"] is None
    for key in ("decode_tok_per_sec", "tokens_per_sec_per_chip",
                "step_time_s"):
        assert rec["detail"][key] is None, key
    assert rec["detail"]["final_loss"] > 0


def test_bench_refuses_a_machine_without_a_chip():
    """No ``--cpu``, no TPU: non-zero exit before anything is measured."""
    rc, rec = _run_bench()
    assert rc != 0
    assert rec["detail"]["ok"] is False and rec["value"] is None
    assert "needs a TPU" in rec["detail"]["error"]


def test_graft_entry_compiles():
    import jax

    sys.path.insert(0, REPO_ROOT)
    import __graft_entry__ as g

    fn, args = g.entry()
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.cost_analysis() is not None
