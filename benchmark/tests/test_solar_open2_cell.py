"""What ISSUE 57 added for ``solar-open2-250b.serve-longctx``: the
configuration file against the catalog row, the bytes the issue reckoned,
``costs_delta`` on hand-computed numbers, the new readers on hand-made spans
and operations (no roofline over 100; a program that names nothing reports
nothing), the traffic file against the mix it copies, and the cell rehearsed
through the real command line - one chip's share of the experts held, the
program against its share's reference under the cell's own limits.
(Program against reference in float32: ``tests/test_solar_open2.py``,
tier-1.)"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.harness import costs_delta as costs
from benchmark.harness import manifest
from benchmark.harness import program_spans as ps
from benchmark.harness.trace import Op, Trace

from test_program_spans import _Cell, span

CELL = "solar-open2-250b.serve-longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = types.SimpleNamespace(hbm_bytes_per_s=819e9, bf16_flops=197e12)
NEW = ("serve_delta_share", "serve_delta_state_share",
       "delta_decode_roofline", "delta_chunk_roofline")


def test_published_is_the_catalog_row_and_the_cut_is_depth_experts_context():
    data = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "solar-open2-250b.json"))
    cut = {"num_hidden_layers": 4, "num_experts": 40,
           "max_position_embeddings": 32768}
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"Solar-Open2-250B"' in ln)
        added = {"num_local_experts": 320}
        assert data["published"] == {**row["config"], **added}
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
    # the widths the issue names, uncut
    assert (data["hidden_size"], data["num_attention_heads"],
            data["num_key_value_heads"], data["head_dim"]) == (4096, 64, 8,
                                                               128)
    assert data["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert (data["moe_intermediate_size"], data["n_routed_experts"],
            data["num_experts_per_tok"], data["vocab_size"]) == (
        1280, 320, 8, 196608)
    for said in ("96 chips", "8 chips share each layer",
                 "one pipeline stage of 12", "one of the eight"):
        assert said in data["deployment"], said
    for key in ("num_local_experts", "kda_low_rank", "kda_layer", "kda_init",
                "gqa_layer", "gqa_layers", "router", "experts", "norm",
                "vocab_size", "deployment_tables", "state_dtype", "weights"):
        assert key in data["assumed"], key
    entry = next(m for m in cell.manifest["workloads"] if m["name"] == CELL)
    assert entry["traffic"] == "longctx-state-closed-16"
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert "1/8" in entry["why"] and "attention" in entry["why"]
    engine = cell.role["engine"]
    assert (engine["split_prefill_chunk"], engine["prefill_bucket"]) == (
        512, 64)
    assert engine["ragged"] == {
        "max_tracked_sequences": 16, "max_ragged_batch_size": 16,
        "memory_config_blocks": 3152, "block_size": 128}
    assert cell.role["scheduler"] == {"decode_quantum": 1,
                                      "max_admissions_per_tick": 1}
    assert cell.role["program_options"] == {"state_dtype": "float32",
                                            "drop_tokens": False}


def test_the_traffic_is_the_long_context_mix_with_other_probes():
    """``longctx-state-closed-16`` equals ``longctx-closed-16`` in everything
    that defines the mix and differs in its probes (and its ``why``)."""
    load = lambda name: manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "traffic", name + ".json"))
    mine, theirs = load("longctx-state-closed-16"), load("longctx-closed-16")
    same = lambda t: {k: v for k, v in t.items()
                      if k not in ("why", "probes", "rehearsal")}
    assert same(mine) == same(theirs)
    assert same(mine["rehearsal"]) == same(theirs["rehearsal"])
    assert mine["probes"] == [[1024, 8], [6144, 8], [2048, 96]]
    assert (mine["clients"], mine["kind"], mine["role"]) == (
        16, "closed_loop", "serve")


def test_the_bytes_are_the_issues():
    """Parameters and pools from the shapes the program builds (no array is
    made): 9.45 GB of weights, 1.65 GB of KV, 0.24 GB of state - the issue's
    11.3 GB."""
    import jax
    import jax.numpy as jnp

    cell = manifest.Cell(CELL)
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    module = cell.family.module()
    params = jax.eval_shape(
        lambda k: module.init(cfg, k, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    size = lambda tree: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                            for a in jax.tree.leaves(tree))
    tables = size({k: params[k] for k in ("embed", "lm_head")})
    assert tables == 2 * 196608 * 4096 * 2
    weights = size(params)
    assert weights == pytest.approx(9.45e9, rel=3e-3)
    assert size(params["delta"]["moe"]["w_up"]) == 3 * 40 * 4096 * 1280 * 2
    ragged = cell.role["engine"]["ragged"]
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, ragged["memory_config_blocks"], ragged["block_size"],
        slots=ragged["max_tracked_sequences"]))
    assert cache["delta"].shape == (3, 17, 144, 8192)
    assert size({k: cache[k] for k in "kv"}) == pytest.approx(1.653e9,
                                                              rel=1e-3)
    assert size(cache["delta"]) == pytest.approx(0.2406e9, rel=1e-3)
    assert module.state_slot_bytes(cfg) == 3 * 144 * 8192 * 4
    assert 11.2e9 < weights + size(cache) < 11.5e9
    assert cfg.layer_types == ("attention", "delta", "delta", "delta")
    assert cfg.experts_held == (0, 40) and cfg.num_experts == 320


def test_costs_count_the_recurrence_whatever_runs_it():
    m, role = manifest.Cell(CELL).model, manifest.Cell(CELL).role
    assert costs.layers(m) == 3
    assert costs.layers({**m, "num_hidden_layers": 48}) == 36
    assert costs.state_bytes_per_row(m, role) == 64 * 128 * 128 * 4
    assert costs.decode_update_floor_bytes(m, role, 16) == \
        2 * 16 * 4_194_304
    # 16 rows, three layers: 0.49 ms at the HBM peak
    assert 3 * costs.decode_update_floor_bytes(m, role, 16) / 819e9 \
        == pytest.approx(0.49e-3, rel=0.01)
    assert costs.chunk_flops(m, 512) == 512 * 6 * 64 * 128 * 128
    assert costs.chunk_floor_s(m, role, 512, PEAKS) == pytest.approx(
        512 * 6 * 64 * 128 * 128 / 197e12)
    # a short chunk is bound by its state's one read and one write
    assert costs.chunk_floor_s(m, role, 64, PEAKS) == pytest.approx(
        2 * 4_194_304 / 819e9)
    low = {**role, "program_options": {"state_dtype": "bfloat16"}}
    assert costs.state_bytes_per_row(m, low) == 2_097_152


# -- the readers, on hand-made spans and operations -------------------------- #
def synthetic(named=True):
    """Two ticks of a mixed program: 15 live decode rows and a 512-row
    chunk; the three KDA layers' chunked forms take 9 ms (their state's read
    and write 0.1 ms of it), their state updates 0.8 ms, their projections
    3 ms and convolutions 1 ms, the GQA layer 4 ms, the experts 8 ms."""
    cell = manifest.Cell(CELL)
    spans, ops = [], []
    for t0 in (0, 50_000_000):
        args = dict(ssm_rows=15, ssm_tokens=15, delta_rows=15,
                    delta_chunk_rows=512) if named else {}
        spans += [span("sched_tick", t0, t0 + 45_000_000),
                  span("decode_step", t0 + 10, t0 + 44_000_000, batch=15,
                       chunk_tokens=512, **args)]
        body = "jit(decode_chunk)/kv_write/while/body/while/body/"
        scope = lambda name: body + "attn/" + (name + "/" if named else "")
        ms = lambda a, b: (t0 + int(a * 1e6), t0 + int(b * 1e6))
        ops += [(Op("state_rows_read.3", *ms(1, 1.05), "mosaic"),
                 scope("delta_chunk") + "pallas_call"),
                (Op("fusion.40", *ms(1.05, 9.95), "xla"),
                 scope("delta_chunk") + "while/body/dot_general"),
                (Op("state_rows_write.5", *ms(9.95, 10), "mosaic"),
                 scope("delta_chunk") + "pallas_call"),
                (Op("delta_decode_update.7", *ms(10, 10.8), "mosaic"),
                 scope("delta_state") + "pallas_call"),
                (Op("fusion.3", *ms(11, 14), "xla"),
                 scope("delta_proj") + "dot_general"),
                (Op("fusion.4", *ms(14, 15), "xla"),
                 scope("delta_conv") + "mul"),
                (Op("paged_prefill.2", *ms(15, 19), "mosaic"),
                 body + "attn/pallas_call"),
                (Op("moe_grouped_matmul.9", *ms(19, 27), "mosaic"),
                 body + "moe_experts/pallas_call")]
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
    busy = 2 * (9.0 + 0.8 + 3 + 1 + 4 + 8)
    floor = 3 * costs.decode_update_floor_bytes(m, role, 30) / 819e9
    got = read(ctx, "delta_decode_roofline")
    assert got == pytest.approx(100 * floor / (2 * 0.8e-3)) and got < 100
    floor = 3 * 2 * costs.chunk_floor_s(m, role, 512, PEAKS)
    got = read(ctx, "delta_chunk_roofline")
    # the SCOPE's time: the row-table kernels and what lies between them
    assert got == pytest.approx(100 * floor / (2 * 9e-3)) and got < 100
    assert read(ctx, "serve_delta_state_share") == pytest.approx(
        100 * 2 * 9.8 / busy)
    assert read(ctx, "serve_delta_share") == pytest.approx(
        100 * 2 * 13.8 / busy)
    # the accepted readers book the same operations as they stand: both
    # kinds of mixer under ``attn``
    assert read(ctx, "serve_attn_share") == pytest.approx(
        100 * 2 * 17.8 / busy)


@pytest.mark.parametrize("name", NEW)
def test_a_program_that_names_nothing_reports_nothing(name):
    """A program without the span arguments and the scopes (the parent's
    cannot run the cell at all; any other family's has neither): every new
    reader returns None and the line leaves the metric out."""
    assert read(synthetic(named=False), name) is None
    if name != "delta_decode_roofline":
        return
    # nor where the spans say it and the trace holds no such kernel
    ctx = synthetic()
    plane = "/device:TPU:0"
    ctx["trace"] = Trace(
        {plane: [op for op in ctx["trace"].devices[plane]
                 if not op.name.startswith("delta")]},
        {plane: []}, [("window", 0, 100_000_000)])
    assert read(ctx, name) is None


def test_the_metrics_are_in_the_manifest_under_their_layers():
    cell = manifest.Cell(CELL)
    mine = {m["name"]: m for m in cell.metrics("per_layer")}
    ends = [m["name"] for m in cell.metrics("end_to_end")]
    assert set(NEW) <= set(mine)
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert set(mine[name]) == {"name", "unit", "better", "source",
                                   "layer", "moves", "workloads"}
    for m in mine.values():
        assert m["moves"] in ends, m
    assert mine["delta_decode_roofline"]["layer"] == "Kernels"
    assert mine["delta_chunk_roofline"]["layer"] == "Kernels"
    assert mine["serve_delta_share"]["layer"] == "Model step"
    # the rate spread 0.86 % in one set of six (half its bound is 0.75): the
    # cell reports the tail alone, as Nemotron's and Brumby's do
    assert ends == ["itl_p99_ms", "setup_s"]
    for name in ("serve_ffn_share", "serve_chunk_tick_share",
                 "decode_step_ms_p50", "serve_attn_share",
                 "serve_mosaic_share", "serve_drain_tick_share",
                 "serve_kv_write_share"):
        assert name in mine, name
    # dead readers, and what reads another family's kernels or keys
    for name in ("prefill_chunk_ms_p50", "sched_host_ms_p50",
                 "serve_moe_router_share", "moe_padded_row_share",
                 "moe_experts_roofline", "ssm_decode_roofline",
                 "serve_ssm_share", "serve_retention_share",
                 "mla_decode_roofline"):
        assert name not in mine, name
    new = [m for m in cell.manifest["per_layer"] if m["name"] in NEW]
    assert cell.manifest["per_layer"][-4:] == new
    assert cell.manifest["workloads"][-1]["name"] == CELL
    assert cell.manifest["configs"][-1]["name"] == "solar-open2-250b"
    assert sum(w["chips"] == 4 for w in cell.manifest["workloads"]) == 1


def test_quiet_chunked_rows_cannot_carry_a_fault_of_the_decoded_rows():
    """64 chunked rows and 96 decoded ones, as the cell's probes have them:
    a fault that moves every decoded row and no chunked one is beyond the
    decoded rows' limits, whatever the chunked rows read; one that leaves
    the first decoded rows clean is beyond their median's."""
    from benchmark.reference import solar_open2 as ref

    role = manifest.Cell(CELL).role["held"]
    limits = {k: v for k, v in role.items() if k != "why"}
    assert set(limits) == {key for key, _, _ in ref.HELD}
    rng = np.random.default_rng(0)
    want = rng.normal(size=(160, 512)).astype(np.float32)
    noise = lambda scale: rng.normal(size=want.shape).astype(
        np.float32) * scale
    quiet = want + noise(0.5 * min(limits.values()))
    assert ref.disagreements(ref.held(quiet, want, 96), limits) == []
    loud = quiet.copy()
    loud[-96:] += noise(1.0)[-96:]
    why = ref.disagreements(ref.held(loud, want, 96), limits)
    assert len(why) == 2 and all("decoded" in w for w in why)
    loud = quiet.copy()
    loud[:64] += noise(1.0)[:64]
    why = ref.disagreements(ref.held(loud, want, 96), limits)
    assert len(why) == 2 and all("chunked" in w for w in why)
    # a fault that starts 20 tokens into the decode leaves a fifth of the
    # decoded rows clean: the quiet row passes, the median row does not
    late = quiet.copy()
    late[-76:] += noise(1.0)[-76:]
    why = ref.disagreements(ref.held(late, want, 96), limits)
    assert len(why) == 1 and "decoded" in why[0] and "median" in why[0]
    assert ref.decode_rows(1031) == ref.decode_rows(6151) == 96


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
    # the decoded rows are judged by themselves, under limits of their own
    assert all(0 < ln["decode_rows"] < ln["rows"]
               and ln["decode_logits_mean_abs_diff"]
               <= ln["limits"]["decode_logits_mean_abs_diff"]
               and ln["decode_median_row_mean_abs_diff"]
               <= ln["limits"]["decode_median_row_mean_abs_diff"]
               for ln in held)
    assert not any(ln.get("compiles_in_window") for ln in lines)
    assert "serve_chunk_tick_share" in result["rehearsal"]
