"""What ISSUE 50 added for ``nemotron-3-nano-30b-a3b.serve-reason-64``: the
configuration file against the catalog row, the bytes the issue reckoned,
``costs_nemotron_h`` on hand-computed numbers, the three new readers on
hand-made spans and operations (no roofline over 100; a program that names
nothing reports nothing), and the cell rehearsed through the real command
line. (Program against reference: ``tests/test_nemotron_h.py``, tier-1.)"""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import costs_nemotron_h as costs
from benchmark.harness import manifest
from benchmark.harness import program_spans as ps
from benchmark.harness.trace import Op, Trace

from test_program_spans import _Cell, span

CELL = "nemotron-3-nano-30b-a3b.serve-reason-64"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = types.SimpleNamespace(hbm_bytes_per_s=819e9, bf16_flops=197e12)
NEW = ("ssm_grouped_decode_roofline", "moe_relu2_experts_roofline",
       "ssm_chunk_scan_share")


def test_published_is_the_catalog_row_and_nothing_is_cut_but_two_keys():
    data = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "nemotron-3-nano-30b-a3b.json"))
    cut = {"num_experts": 8, "max_position_embeddings": 8192}
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in ln)
        assert data["published"] == row["config"]      # verbatim
        assert data["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert data[key] == cut.get(key, value), key
    assert data["reduced"] == list(cut)
    assert data["roles"]["serve"]["model"] == cut
    cell = manifest.Cell(CELL)
    assert cell.model == {**data["published"], **cut}
    assert {k: data[k] for k in cell.model} == cell.model
    for key in manifest.WIDTH_KEYS:
        if key in data["published"]:
            assert data[key] == data["published"][key], key
    # no depth is cut: all 52 layers in the published 23 : 23 : 6
    pattern = data["hybrid_override_pattern"]
    assert len(pattern) == data["num_hidden_layers"] == 52
    assert costs.layer_counts(cell.model) == {
        "mamba": 23, "experts": 23, "attention": 6}
    assert data["num_experts"] >= 8 and data["n_routed_experts"] == 128
    for said in ("16 chips share each layer", "8 of the 128",
                 "all 52 layers"):
        assert said in data["deployment"]
    for key in ("num_experts", "attention", "mamba", "router", "experts",
                "vocab_size", "state_dtype", "weights"):
        assert key in data["assumed"], key
    assert "NO rotary" in data["assumed"]["attention"]
    entry = next(m for m in cell.manifest["workloads"] if m["name"] == CELL)
    assert entry["traffic"] == "reason-closed-64" and entry["chips"] == 1
    t = cell.traffic
    assert (t["clients"], t["size_table"], t["stagger_first"]) == (
        64, 64, True)
    assert t["prompt_tokens"] == {"min": 256, "max": 2048, "spacing": "log"}
    assert t["answer_tokens"] == {"min": 512, "max": 2048, "spacing": "log"}
    assert (t["warmup_ticks"], t["trace_units"]) == (256, 64)
    assert t["probes"] == [[256, 8], [2048, 8], [640, 96]]


def test_the_bytes_are_the_issues():
    """ISSUE 50's arithmetic: a Mamba layer 38.74 M parameters, an attention
    layer 23.40 M, an expert layer 0.34 M router + 19.96 M shared + 8 x 9.98
    M, embedding + head 704.6 M: 8.09 GB of weights at the published widths
    (8.22 GB as laid out: the bank's 1856 columns in whole lane tiles); the
    state 2.23 MB a slot a layer, 3.33 GB; the KV pools 1.62 GB."""
    cell = manifest.Cell(CELL)
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    module = cell.family.module()
    shapes = jax.eval_shape(
        lambda k: module.init(cfg, k, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    count = lambda tree: sum(int(x.size) for x in jax.tree.leaves(tree))
    assert round(count(shapes["mamba"]) / 23 / 1e6, 2) == 38.74
    assert round(count(shapes["attn"]) / 6 / 1e6, 2) == 23.40
    moe = shapes["moe"]
    assert moe["router"].shape == (23, 2688, 128)
    assert moe["router"].dtype == moe["router_bias"].dtype == jnp.float32
    assert moe["w_up"].shape == (23, 8, 2688, 1920) and cfg.expert_lanes == 1920
    assert costs.expert_params(cell.model) == 2 * 2688 * 1856 == 9_977_856
    assert count({k: moe[k] for k in ("shared_w_up", "shared_w_down")}) \
        == 23 * 2 * 2688 * 3712
    assert round(count({"e": shapes["embed"], "h": shapes["lm_head"]})
                 / 1e6, 1) == 704.6
    published = count(shapes) - 23 * 8 * 2 * 2688 * (1920 - 1856)
    nbytes = lambda n: 2 * n + 2 * 23 * 2688 * 128      # the router float32
    assert round(nbytes(published) / 1e9, 2) == 8.09     # (the issue: 8.08)
    assert round(nbytes(count(shapes)) / 1e9, 2) == 8.22
    ragged = cell.role["engine"]["ragged"]
    pools = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, ragged["memory_config_blocks"], ragged["block_size"],
        slots=ragged["max_tracked_sequences"]))
    assert pools["ssm"].shape == (23, 65, 136, 4096)
    assert pools["ssm"].dtype == jnp.float32 and cfg.tail_part == (
        128, 8, 2304)
    assert round(pools["ssm"].size * 4 / 1e9, 2) == 3.33
    assert module.state_slot_bytes(cfg) == 23 * 136 * 4096 * 4
    assert pools["k"].shape == (6, 8256, 2, 32, 128)
    assert round(2 * pools["k"].size * 2 / 1e9, 2) == 1.62
    # 64 sequences at the longest context and a spare block each
    assert ragged["memory_config_blocks"] == 64 * (4096 // 32 + 1)
    # the fullest device holds at least 12 GB
    assert nbytes(count(shapes)) + pools["ssm"].size * 4 \
        + 4 * pools["k"].size > 12e9


def test_costs_count_layers_by_kind_and_an_expert_as_two_matrices():
    cell = manifest.Cell(CELL)
    m, role = cell.model, cell.role
    assert costs.state_bytes_per_row(m, role) == 64 * 64 * 128 * 4
    assert costs.decode_update_floor_bytes(m, role, 64) \
        == 2 * 64 * 2_097_152
    # 64 rows x 6 of 128: each held expert is reached by a row with 6 / 128
    reached = 8 * (1 - (1 - 6 / 128) ** 64)
    assert costs.held_experts_reached(m, 64) == pytest.approx(reached)
    assert 0.94 < reached / 8 < 0.96            # the issue's "95 % of them"
    routed = 64 * 6 * 8 // 128                  # what the span says: 24
    assert costs.call_tokens(m, routed) == 64
    floor = costs.bank_floor_s(m, 64, PEAKS)
    assert floor == pytest.approx(reached * 9_977_856 * 2 / 819e9)
    # a mixed call's 576 rows: every held expert, still the weights' time
    assert costs.bank_floor_s(m, 576, PEAKS) \
        == pytest.approx(8 * 9_977_856 * 2 / 819e9, rel=1e-6)
    # a call so long that the operations lead
    long = 2_000_000
    assert costs.bank_floor_s(m, long, PEAKS) == pytest.approx(
        2 * long * 6 * 8 / 128 * 9_977_856 / 197e12)
    # MEASURED shares in the place of the uniform router's: an expert no row
    # chooses is not reached, one every row chooses is reached by one row
    shares = [0.0, 1.0] + [6 / 128] * 6
    assert costs.held_experts_reached(m, 64, shares) == pytest.approx(
        1 + reached * 6 / 8)
    assert costs.held_experts_reached(m, 1, shares) == pytest.approx(
        1 + 6 * 6 / 128)
    assert costs.bank_floor_s(m, 64, PEAKS, shares) == pytest.approx(
        (1 + reached * 6 / 8) * 9_977_856 * 2 / 819e9)


# -- the readers, on hand-made spans and operations -------------------------- #
def synthetic(named=True):
    """Two ticks of a mixed program: 60 live decode rows and a 512-row chunk
    (576 rows through the bank); the state update takes 500 ns, the chunked
    scan and the state's rows 300 ns, the bank 400 ns of each tick's 4000."""
    model = manifest.Cell(CELL).model
    spans, ops = [], []
    for t0 in (0, 5000):
        args = dict(ssm_rows=60, ssm_tokens=60, moe_rows_routed=216,
                    moe_rows_computed=1024) if named else {}
        spans += [span("sched_tick", t0, t0 + 4500),
                  span("decode_step", t0 + 10, t0 + 4400, batch=60,
                       chunk_tokens=512, **args)]
        body = "jit(decode_chunk)/kv_write/while/body/"
        state = body + ("attn/ssm_state/" if named else "attn/")
        ops += [(Op("ssm_decode_update.7", t0 + 100, t0 + 600, "mosaic"),
                 state + "pallas_call"),
                (Op("fusion.3", t0 + 600, t0 + 900, "xla"),
                 state + "dot_general"),
                (Op("moe_grouped_matmul.2", t0 + 900, t0 + 1300, "mosaic"),
                 body + "moe_experts/pallas_call"),
                (Op("fusion.9", t0 + 1300, t0 + 4100, "xla"),
                 body + "attn/" + ("ssm_proj/" if named else "")
                 + "dot_general")]
    plane = "/device:TPU:0"
    trace = Trace({plane: [op for op, _ in ops]}, {plane: []},
                  [("window", 0, 10000)])
    role = manifest.Cell(CELL).role
    return {"cell": _Cell("synthetic", model=model, role=role),
            "trace": trace, "peaks": PEAKS,
            "program_spans": ps.Program(ps.link(spans), {plane: ops})}


def read(ctx, name):
    definition = manifest.metric_definition(name)
    return manifest.reader(definition["reader"]).read(
        ctx, **definition.get("params", {}))


def test_the_new_readers_on_hand_made_spans():
    ctx = synthetic()
    m, role = ctx["cell"].model, ctx["cell"].role
    floor = 23 * costs.decode_update_floor_bytes(m, role, 120) / 819e9
    assert read(ctx, "ssm_grouped_decode_roofline") == pytest.approx(
        100 * floor / (2 * 500e-9))
    # the bank's floor counts by what the run's probes MEASURED of the
    # routing, layer by layer; none routed: nothing to count by
    from benchmark.reference import nemotron_h as ref

    ref._ROUTED.update(rows=0, chosen=0)
    assert read(ctx, "moe_relu2_experts_roofline") is None
    loads = [np.zeros((40, 8), bool) for _ in range(23)]
    loads[0][:20, 1] = True         # layer 0: expert 1 by half the rows
    loads[5][:, :] = True           # layer 5: every expert by every row
    ref.note_routing(loads, 40)
    ref.note_routing(loads, 40)
    assert ref.routed_shares().shape == (23, 8)
    floor = 2 * (costs.bank_floor_s(m, 576, PEAKS, [0.5] + [0.0] * 7)
                 + costs.bank_floor_s(m, 576, PEAKS, [1.0] * 8))
    assert read(ctx, "moe_relu2_experts_roofline") == pytest.approx(
        100 * floor / (2 * 400e-9))
    ref._ROUTED.update(rows=0, chosen=0)
    assert read(ctx, "ssm_chunk_scan_share") == pytest.approx(
        100 * 300 / 4000)
    # the accepted readers book the same operations as they stand
    assert read(ctx, "serve_ssm_state_share") == pytest.approx(
        100 * 800 / 4000)
    assert read(ctx, "serve_ssm_share") == pytest.approx(100 * 3600 / 4000)
    assert read(ctx, "serve_ffn_share") == pytest.approx(100 * 400 / 4000)


@pytest.mark.parametrize("name", NEW)
def test_a_program_that_names_nothing_reports_nothing(name):
    """A program without the span arguments and the scopes (the parent's
    cannot run the cell at all; any other family's has neither): every new
    reader returns None and the line leaves the metric out."""
    ctx = synthetic(named=False)
    if name == "ssm_chunk_scan_share":
        assert read(ctx, name) is None
        return
    assert read(ctx, name) is None
    # nor where the spans say it and the trace holds no such kernel
    ctx = synthetic()
    plane = "/device:TPU:0"
    ctx["trace"] = Trace(
        {plane: [op for op in ctx["trace"].devices[plane]
                 if not op.name.startswith(("ssm_decode", "moe_grouped"))]},
        {plane: []}, [("window", 0, 10000)])
    assert read(ctx, name) is None


def test_the_metrics_are_in_the_manifest_under_their_layers():
    cell = manifest.Cell(CELL)
    mine = {m["name"]: m for m in cell.metrics("per_layer")}
    assert set(NEW) <= set(mine)
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
    assert mine["ssm_grouped_decode_roofline"]["layer"] == "Kernels"
    assert mine["ssm_chunk_scan_share"]["layer"] == "Model step"
    # NOT ``serve_tokens_per_s``: in this cell the rate is the host's
    # stalled ticks (PERF.md section 6, PR 50), so the cell is on no list of
    # a metric that moves it and every metric of its own moves the tail
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "itl_p99_ms", "setup_s"]
    assert {m["moves"] for m in mine.values()} == {"itl_p99_ms"}
    for name in ("serve_ssm_share", "serve_ssm_state_share",
                 "serve_ffn_share", "serve_chunk_tick_share",
                 "decode_step_ms_p50", "serve_attn_share"):
        assert name in mine, name
    # dead readers and those whose costs read another configuration's keys
    for name in ("prefill_chunk_ms_p50", "sched_host_ms_p50",
                 "decode_hbm_share", "paged_decode_roofline",
                 "decode_live_tile_share", "ssm_decode_roofline",
                 "moe_experts_roofline"):
        assert name not in mine, name
    assert len(cell.manifest["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in cell.manifest["workloads"]) == 1


def test_quiet_chunked_rows_cannot_carry_a_fault_of_the_decoded_rows():
    """64 chunked rows and 96 decoded ones, as the cell's probes have them:
    a fault that moves every decoded row and no chunked one is beyond the
    decoded rows' limit, whatever the chunked rows read."""
    from benchmark.reference import nemotron_h as ref

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
    loud[-96:] += noise(0.1)[-96:]
    why = ref.disagreements(ref.held(loud, want, 96), limits)
    assert len(why) == 1 and "decoded" in why[0]
    loud = quiet.copy()
    loud[:64] += noise(0.1)[:64]
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
