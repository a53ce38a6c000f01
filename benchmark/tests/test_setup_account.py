"""What ISSUE 53 added: the five ``setup_*`` metrics (reader
``setup_account`` over the program's own compile account) through the real
command line. (The account, and the reader on hand-made contexts:
``tests/test_compile_account.py``, tier-1.)"""

import json
import os
import subprocess
import sys

from benchmark.harness import manifest

CELL = "mistral-7b.serve-chat"
METRICS = {"setup_trace_lower_s": "s", "setup_backend_compile_s": "s",
           "setup_cache_misses": "count", "setup_programs_compiled": "count",
           "setup_monitor_analysis_s": "s"}


def test_every_cell_reports_them_and_they_move_setup_s():
    entries = {m["name"]: m for m in manifest.manifest()["per_layer"]}
    for name, unit in METRICS.items():
        assert entries[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": "Start-up",
            "moves": "setup_s"}               # no ``workloads``: every cell
        assert manifest.metric_definition(name)["reader"] == "setup_account"


def test_the_chat_cell_rehearses_with_all_five():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "5300000011", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and "metrics" not in last
    got = last["rehearsal"]
    for name, unit in METRICS.items():
        assert got[name]["unit"] == unit and got[name]["value"] >= 0, name
    (e2e,) = [ln for ln in lines
              if ln.get("phase") == "end_to_end_of_traced_run"]
    seconds = sum(got[name]["value"] for name, unit in METRICS.items()
                  if unit == "s")
    assert 0 < seconds < e2e["setup_s"]["value"]
    assert got["setup_programs_compiled"]["value"] > 0
    assert got["setup_trace_lower_s"]["value"] > 0
