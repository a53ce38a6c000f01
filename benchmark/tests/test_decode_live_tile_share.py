"""What ISSUE 27 added: ``decode_live_tile_share``, the mean over the traced
window's ``decode_step`` spans of the share of the ``paged_decode`` grid's KV
tiles that hold live context (the program's ``attn_live_tile_share``), read
by the ``span_arg`` reader the benchmark already had."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest
from benchmark.harness import program_spans as ps
from benchmark.harness.trace import Trace

from test_program_spans import OLDER, RECORDED, _Cell, _ctx, span

NAME = "decode_live_tile_share"
SERVE_CELLS = ["mixtral-8x7b.serve-longprompt", "mistral-7b.serve-chat",
               "olmoe-1b-7b.serve-longprompt"]


def read(ctx):
    definition = manifest.metric_definition(NAME)
    return manifest.reader(definition["reader"]).read(
        ctx, **definition.get("params", {}))


def test_the_definition_is_a_span_argument_read_by_an_existing_reader():
    definition = manifest.metric_definition(NAME)
    assert definition == {
        "name": NAME, "reader": "span_arg",
        "params": {"span": "decode_step", "arg": "attn_live_tile_share"}}
    entry = next(m for m in manifest.manifest()["per_layer"]
                 if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_span", "layer": "Kernels",
                     "moves": "itl_p99_ms", "workloads": SERVE_CELLS}
    for cell in SERVE_CELLS:
        assert NAME in {m["name"]
                        for m in manifest.Cell(cell).metrics("per_layer")}


def test_the_share_is_the_mean_over_the_windows_decode_steps():
    """Three decode steps as the program's spans carry them, one of them
    outside the window; a chunk's span has no such argument."""
    spans = [span("decode_step", 100, 900, batch=32, attn_tiles_live=81,
                  attn_tiles_grid=128, attn_live_tile_share=81 / 128),
             span("prefill_chunk", 1000, 1900, tokens=256),
             span("decode_step", 2000, 2900, batch=32, attn_tiles_live=64,
                  attn_tiles_grid=128, attn_live_tile_share=0.5),
             span("decode_step", 9000, 12000, batch=32, attn_tiles_live=128,
                  attn_tiles_grid=128, attn_live_tile_share=1.0)]
    plane = "/device:TPU:0"
    ctx = {"cell": _Cell("synthetic"),
           "trace": Trace({plane: []}, {plane: []}, [("window", 0, 10000)]),
           "program_spans": ps.Program(ps.link(spans), {plane: []})}
    assert read(ctx) == pytest.approx(100 * (81 / 128 + 0.5) / 2)


@pytest.mark.parametrize("path", [OLDER, RECORDED])
def test_the_parent_reports_nothing(monkeypatch, path):
    """No ``dstpu:`` spans at all (the older file), or ``decode_step`` spans
    without the argument (the program recorded at PR 24): the metric is left
    out of the line, it is not zero."""
    ctx = _ctx(monkeypatch, path)
    assert read(ctx) is None
    assert read({"cell": ctx["cell"], "trace": None}) is None


def test_a_served_cell_reports_it_through_the_real_command_line():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral-7b.serve-chat", "--seed", "3000000027", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = [json.loads(ln) for ln in proc.stdout.splitlines()
            if ln.startswith("{")][-1]
    assert last["correct"] is True and last["failed"] == 0
    share = last["rehearsal"][NAME]
    assert share["unit"] == "%" and 0 < share["value"] <= 100
