"""``BENCHMARK.json`` against the contract it was written to, and the proof
that the harness is data: a fifth cell, a new traffic file and a new
per-layer metric are added to a temporary copy by adding files and
appending entries, and the real command line runs them."""

import json
import os
import re
import shutil
import subprocess
import sys


from benchmark.harness import manifest

ROOT = manifest.ROOT
B = manifest.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|"
                   r"head_dim|expansion|experts_per_tok")


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["benchmark"]
    assert B["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_the_full_check_fits_the_drivers_budget_at_24_cells():
    cells = 24
    runs = 2 + 14 * cells
    assert runs * (B["run_seconds"] + 60) + cells * 2 * 90 + 1200 <= 43200


def test_configs():
    used = {w["config"] for w in B["workloads"]}
    files = set()
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
        data = manifest.load_json(os.path.join(ROOT, c["file"]))
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        for role in data["roles"].values():     # a cut touches no width
            assert set(role["model"]) <= set(c["reduced"])


def test_workloads():
    names, pairs = set(), set()
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["name"] not in names
        names.add(w["name"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4) and one_line(w["why"])
        manifest.Cell(w["name"])                # every file it names loads
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, len(B["workloads"]) // 4)
    assert 1 <= len(B["workloads"]) <= 24


def reported_in(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_metrics():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    seen = set()
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = [w["name"] for w in B["workloads"]]
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert one_line(m["layer"])
        moved = e2e[m["moves"]]
        for cell in cells:          # what it moves is reported wherever it is
            if reported_in(m, cell):
                assert reported_in(moved, cell), (m["name"], cell)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        definition = manifest.metric_definition(m["name"])
        assert hasattr(manifest.reader(definition["reader"]), "read")
    for cell in cells:
        mine = [m for m in B["end_to_end"] if reported_in(m, cell)]
        assert {"setup_s"} < {m["name"] for m in mine}
        assert any(reported_in(m, cell) for m in B["per_layer"])


def test_no_branch_on_a_workloads_name_anywhere_in_the_harness():
    names = [w["name"] for w in B["workloads"]] + \
        [c["name"] for c in B["configs"]]
    for folder, _, files in os.walk(manifest.BENCH_DIR):
        if os.path.basename(folder) in ("tests", "__pycache__"):
            continue
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(folder, f)).read()
                assert not any(f'"{n}"' in text or f"'{n}'" in text
                               for n in names), f


def test_the_program_is_read_through_public_names_only():
    """A later PR may refactor the program and may not edit the benchmark,
    so no file here reaches for an underscore name of anything but
    itself."""
    private = re.compile(r"(?<![A-Za-z0-9_])(?!self\b)[A-Za-z_][A-Za-z0-9_\]\)]*"
                         r"\._[a-z]")
    for folder, _, files in os.walk(manifest.BENCH_DIR):
        if os.path.basename(folder) in ("tests", "__pycache__"):
            continue
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(folder, f)).read()
                assert not private.findall(text), (f, private.findall(text))


NEW_TRAFFIC = {
    "kind": "closed_loop", "role": "serve", "why": "added by the test",
    "clients": 3, "prompt_tokens": {"min": 6, "max": 30, "spacing": "linear"},
    "answer_tokens": {"min": 3, "max": 6, "spacing": "log"}, "size_table": 6,
    "stagger_first": False, "warmup_ticks": 3, "trace_units": 4,
    "probes": [[9, 2], [33, 2]], "rehearsal": {}}
NEW_READER = '''"""Ticks of the measured window (added by the test)."""


def read(ctx):
    start, end = ctx["window"]
    return float(end - start)
'''


def test_a_cell_a_traffic_file_and_a_metric_are_added_as_files(tmp_path):
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copytree(manifest.BENCH_DIR, copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "deepspeed_tpu"), copy / "deepspeed_tpu")
    before = {p: p.read_bytes() for p in (copy / "benchmark").rglob("*")
              if p.is_file()}
    # files only ...
    (copy / "benchmark/traffic/test-closed-3.json").write_text(
        json.dumps(NEW_TRAFFIC))
    (copy / "benchmark/readers/ticks_in_window.py").write_text(NEW_READER)
    (copy / "benchmark/metrics/test_ticks_in_window.json").write_text(
        json.dumps({"name": "test_ticks_in_window",
                    "reader": "ticks_in_window"}))
    # ... and entries appended to the manifest
    b = json.loads(json.dumps(B))
    b["workloads"].append({
        "name": "mixtral-8x7b.test-closed-3", "config": "mixtral-8x7b",
        "traffic": "test-closed-3", "chips": 1, "why": "added by the test"})
    for m in b["end_to_end"]:
        if "workloads" in m and m["name"] != "train_tokens_per_s_chip":
            m["workloads"].append("mixtral-8x7b.test-closed-3")
    b["per_layer"].append({
        "name": "test_ticks_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Serve engine + scheduler",
        "moves": "serve_tokens_per_s",
        "workloads": ["mixtral-8x7b.test-closed-3"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(b))
    assert all(p.read_bytes() == data for p, data in before.items())
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mixtral-8x7b.test-closed-3", "--seed", "5", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert "metrics" not in last               # a rehearsal is no measurement
    assert last["rehearsal"]["test_ticks_in_window"]["value"] > 10


def test_refuses_to_measure_without_the_chip(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral-7b.train-2k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if '"correct"' in ln]
    assert "refused" in proc.stderr
