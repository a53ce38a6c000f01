"""What ISSUE 55 added for ``brumby-14b-base.serve-reason-32``: the
configuration file against the catalog row, the bytes the issue reckoned,
``costs_retention`` on hand-computed numbers, the new readers on hand-made
spans and operations (no roofline over 100; a program that names nothing
reports nothing), and the cell rehearsed through the real command line.
(Program against reference: ``tests/test_brumby.py``, tier-1.)"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.harness import costs_retention as costs
from benchmark.harness import manifest
from benchmark.harness import program_spans as ps
from benchmark.harness.trace import Op, Trace

from test_program_spans import _Cell, span

CELL = "brumby-14b-base.serve-reason-32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = types.SimpleNamespace(hbm_bytes_per_s=819e9, bf16_flops=197e12)
NEW = ("serve_retention_share", "serve_retention_state_share",
       "retention_decode_roofline", "retention_chunk_roofline")


def test_published_is_the_catalog_row_and_nothing_is_cut_but_the_depth():
    data = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "brumby-14b-base.json"))
    cut = {"num_hidden_layers": 5}
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"Brumby-14B-Base"' in ln)
        assert data["published"] == row["config"]      # verbatim
        assert data["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert data[key] == cut.get(key, value), key
    assert data["reduced"] == list(cut)
    assert data["roles"]["serve"]["model"] == cut
    cell = manifest.Cell(CELL)
    assert cell.model == {**data["published"], **cut}
    for key in manifest.WIDTH_KEYS:
        if key in data["published"]:
            assert data[key] == data["published"][key], key
    assert data["max_position_embeddings"] == 32768     # as published
    for said in ("2x4", "8 pipeline stages of 5 layers", "ONE stage"):
        assert said in data["deployment"]
    for key in ("retention", "degree", "gate", "normaliser", "projections",
                "state", "switch_over", "qwen_relics", "weights"):
        assert key in data["assumed"], key
    assert "p = 2" in data["assumed"]["degree"]
    assert "KEY-VALUE head" in data["assumed"]["gate"]
    entry = next(m for m in cell.manifest["workloads"] if m["name"] == CELL)
    assert entry["traffic"] == "reason-closed-32" and entry["chips"] == 1
    t = cell.traffic
    assert (t["clients"], t["size_table"], t["stagger_first"]) == (
        32, 64, True)
    assert t["prompt_tokens"] == {"min": 256, "max": 2048, "spacing": "log"}
    assert t["answer_tokens"] == {"min": 512, "max": 2048, "spacing": "log"}
    assert (t["warmup_ticks"], t["trace_units"]) == (256, 64)
    assert t["probes"] == [[256, 8], [2048, 8], [640, 96]]
    engine = cell.role["engine"]
    assert (engine["prefill_bucket"], engine["split_prefill_chunk"]) == (
        64, 512)
    assert engine["ragged"]["max_tracked_sequences"] == 32
    assert engine["ragged"]["max_ragged_batch_size"] == 32
    assert "memory_config_blocks" not in engine["ragged"]   # no KV pool
    assert cell.role["scheduler"] == {"decode_quantum": 1,
                                      "max_admissions_per_tick": 1}
    assert cell.role["program_options"] == {"state_dtype": "float32"}


def test_the_bytes_are_the_issues():
    """The issue's arithmetic, from the program's own shapes (shape
    evaluation only: nothing is allocated)."""
    import jax

    cell = manifest.Cell(CELL)
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    module = cell.family.module()
    params = jax.eval_shape(lambda k: module.init(cfg, k),
                            jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(p.shape))
                             for p in jax.tree.leaves(tree))
    layer = count(params["layers"]) / 5
    assert layer == pytest.approx(330.3e6, rel=1e-3)
    assert count(params["embed"]) == count(params["lm_head"]) == 777_912_320
    weights = 2 * count(params)
    assert weights == pytest.approx(6.41e9, rel=2e-3)
    # the state: 34.08 MB a slot a layer counted symmetric, 35.93 streamed
    assert costs.state_entries(cell.model) == 8256
    assert costs.state_bytes_per_row(cell.model, cell.role) == \
        8 * 8256 * 129 * 4 == 34_080_768
    assert cfg.state_row_bytes == 1032 * 8704 * 4 == 35_930_112
    assert module.state_slot_bytes(cfg) == 5 * 35_930_112
    cache = jax.eval_shape(lambda: module.init_paged_cache(cfg, 512, 32,
                                                           slots=32))
    assert set(cache) == {"ret"}                        # and no KV pool
    pool = 33 * module.state_slot_bytes(cfg)
    assert cache["ret"].shape == (5, 33, 1032, 8704)
    assert pool == pytest.approx(5.93e9, rel=2e-3)
    assert 12.0e9 < weights + pool < 12.7e9


def test_costs_count_the_symmetric_state_whatever_is_streamed():
    m, role = manifest.Cell(CELL).model, manifest.Cell(CELL).role
    assert costs.decode_update_floor_bytes(m, role, 32) == \
        2 * 32 * 34_080_768
    # 32 rows a layer: 2.66 ms at the HBM peak; the issue's 13.3 ms a tick
    assert 5 * costs.decode_update_floor_bytes(m, role, 32) / 819e9 \
        == pytest.approx(13.3e-3, rel=5e-3)
    per_head = 2 * 8256 * 129
    assert costs.chunk_flops(m, 512) == 512 * per_head * 48
    # ~105 MFLOP a row a layer, as the issue reckons
    assert costs.chunk_flops(m, 1) == pytest.approx(102e6, rel=0.03)
    assert costs.chunk_floor_s(m, role, 512, PEAKS) == pytest.approx(
        512 * per_head * 48 / 197e12)
    # a short chunk is bound by its state's one read and one write
    assert costs.chunk_floor_s(m, role, 64, PEAKS) == pytest.approx(
        2 * 34_080_768 / 819e9)
    low = {**role, "program_options": {"state_dtype": "bfloat16"}}
    assert costs.state_bytes_per_row(m, low) == 34_080_768 // 2


# -- the readers, on hand-made spans and operations -------------------------- #
def synthetic(named=True):
    """Two ticks of a mixed program: 30 live decode rows and a 512-row
    chunk; the five layers' state updates take 16 ms, their chunk kernels 3 ms, the
    projections 1 ms and the feed-forward 2 ms of each tick."""
    cell = manifest.Cell(CELL)
    spans, ops = [], []
    for t0 in (0, 50_000_000):
        args = dict(ssm_rows=30, ssm_tokens=30, retention_rows=30,
                    retention_chunk_rows=512) if named else {}
        spans += [span("sched_tick", t0, t0 + 45_000_000),
                  span("decode_step", t0 + 10, t0 + 44_000_000, batch=30,
                       chunk_tokens=512, **args)]
        body = "jit(decode_chunk)/kv_write/while/body/attn/"
        scope = lambda name: body + (name + "/" if named else "")
        ops += [(Op("retention_chunk.3", t0 + 1_000_000, t0 + 4_000_000,
                    "mosaic"), scope("retention_chunk") + "pallas_call"),
                (Op("retention_decode_update.7", t0 + 4_000_000,
                    t0 + 20_000_000, "mosaic"),
                 scope("retention_state") + "pallas_call"),
                (Op("fusion.3", t0 + 20_000_000, t0 + 21_000_000, "xla"),
                 scope("retention_proj") + "dot_general"),
                (Op("fusion.9", t0 + 21_000_000, t0 + 23_000_000, "xla"),
                 "jit(decode_chunk)/kv_write/while/body/ffn/dot_general")]
    plane = "/device:TPU:0"
    trace = Trace({plane: [op for op, _ in ops]}, {plane: []},
                  [("window", 0, 100_000_000)])
    return {"cell": _Cell("synthetic", model=cell.model, role=cell.role),
            "trace": trace, "peaks": PEAKS,
            "program_spans": ps.Program(ps.link(spans), {plane: ops})}


def read(ctx, name):
    definition = manifest.metric_definition(name)
    return manifest.reader(definition["reader"]).read(
        ctx, **definition.get("params", {}))


def test_the_new_readers_on_hand_made_spans():
    ctx = synthetic()
    m, role = ctx["cell"].model, ctx["cell"].role
    floor = 5 * costs.decode_update_floor_bytes(m, role, 60) / 819e9
    got = read(ctx, "retention_decode_roofline")
    assert got == pytest.approx(100 * floor / (2 * 16e-3)) and got < 100
    floor = 5 * 2 * costs.chunk_floor_s(m, role, 512, PEAKS)
    got = read(ctx, "retention_chunk_roofline")
    assert got == pytest.approx(100 * floor / (2 * 3e-3)) and got < 100
    assert read(ctx, "serve_retention_state_share") == pytest.approx(
        100 * 16 / 22)
    assert read(ctx, "serve_retention_share") == pytest.approx(
        100 * 20 / 22)
    # the accepted readers book the same operations as they stand
    assert read(ctx, "serve_attn_share") == pytest.approx(100 * 20 / 22)
    assert read(ctx, "serve_ffn_share") == pytest.approx(100 * 2 / 22)


@pytest.mark.parametrize("name", NEW)
def test_a_program_that_names_nothing_reports_nothing(name):
    """A program without the span arguments and the scopes (the parent's
    cannot run the cell at all; any other family's has neither): every new
    reader returns None and the line leaves the metric out."""
    assert read(synthetic(named=False), name) is None
    if not name.endswith("roofline"):
        return
    # nor where the spans say it and the trace holds no such kernel
    ctx = synthetic()
    plane = "/device:TPU:0"
    ctx["trace"] = Trace(
        {plane: [op for op in ctx["trace"].devices[plane]
                 if not op.name.startswith("retention")]},
        {plane: []}, [("window", 0, 100_000_000)])
    assert read(ctx, name) is None


def test_the_metrics_are_in_the_manifest_under_their_layers():
    cell = manifest.Cell(CELL)
    mine = {m["name"]: m for m in cell.metrics("per_layer")}
    assert set(NEW) <= set(mine)
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] in {m["name"]
                                       for m in cell.metrics("end_to_end")}
    assert mine["retention_decode_roofline"]["layer"] == "Kernels"
    assert mine["retention_chunk_roofline"]["layer"] == "Kernels"
    assert mine["serve_retention_share"]["layer"] == "Model step"
    ends = [m["name"] for m in cell.metrics("end_to_end")]
    assert "setup_s" in ends and "itl_p99_ms" in ends
    for name in ("serve_ffn_share", "serve_chunk_tick_share",
                 "decode_step_ms_p50", "serve_attn_share",
                 "serve_mosaic_share", "serve_drain_tick_share"):
        assert name in mine, name
    # dead readers, and what reads a KV pool or another family's kernels
    for name in ("prefill_chunk_ms_p50", "sched_host_ms_p50",
                 "serve_kv_write_share", "decode_hbm_share",
                 "paged_decode_roofline", "decode_live_tile_share",
                 "ssm_decode_roofline", "serve_ssm_share"):
        assert name not in mine, name
    assert len(cell.manifest["workloads"]) == 11
    assert len(cell.manifest["configs"]) == 9
    assert sum(w["chips"] == 4 for w in cell.manifest["workloads"]) == 1


def test_quiet_chunked_rows_cannot_carry_a_fault_of_the_decoded_rows():
    """64 chunked rows and 96 decoded ones, as the cell's probes have them:
    a fault that moves every decoded row and no chunked one is beyond the
    decoded rows' limit, whatever the chunked rows read."""
    from benchmark.reference import brumby as ref

    role = manifest.Cell(CELL).role["held"]
    limits = {k: v for k, v in role.items() if k != "why"}
    assert set(limits) == {"logits_mean_abs_diff",
                           "decode_logits_mean_abs_diff"}
    rng = np.random.default_rng(0)
    want = rng.normal(size=(160, 512)).astype(np.float32)
    noise = lambda scale: rng.normal(size=want.shape).astype(
        np.float32) * scale
    quiet = want + noise(0.5 * limits["logits_mean_abs_diff"])
    assert ref.disagreements(ref.held(quiet, want, 96), limits) == []
    loud = quiet.copy()
    loud[-96:] += noise(1.0)[-96:]
    why = ref.disagreements(ref.held(loud, want, 96), limits)
    assert len(why) == 1 and "decoded" in why[0]
    loud = quiet.copy()
    loud[:64] += noise(1.0)[:64]
    why = ref.disagreements(ref.held(loud, want, 96), limits)
    assert len(why) == 1 and "chunked" in why[0]
    assert ref.decode_rows(263) == ref.decode_rows(2055) == 96


def test_the_cell_rehearses_through_the_real_command_line():
    out = subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", "3000000017", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln]
    result = lines[-1]
    assert result["correct"] and result["failed"] == 0
    assert "metrics" not in result and "rehearsal" in result
    held = [ln for ln in lines if ln.get("phase") == "held"]
    assert len(held) == 3 and not any(ln["why_not"] for ln in held)
    # the decoded rows are judged by themselves, under a limit of their own
    assert all(0 < ln["decode_rows"] < ln["rows"]
               and ln["decode_logits_mean_abs_diff"]
               <= ln["limits"]["decode_logits_mean_abs_diff"] for ln in held)
    assert not any(ln.get("compiles_in_window") for ln in lines)
    assert "serve_chunk_tick_share" in result["rehearsal"]
