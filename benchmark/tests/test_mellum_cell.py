"""What ISSUE 61 added for ``mellum2-12b-a2.5b-instruct.serve-mixedlen-32``:
the configuration file against the catalog row, the bytes the issue
reckoned, the traffic's two regimes, the new readers on hand-made spans and
operations (no roofline over 100; a program that names nothing reports
nothing), the manifest's entries by MEMBERSHIP (no count of cells,
configurations or metrics), and the cell rehearsed through the real command
line. (Program against reference in float32: ``tests/test_mellum.py``,
tier-1.)"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.harness import costs_moe, costs_window, manifest, sizes
from benchmark.harness import program_spans as ps
from benchmark.harness.trace import Op, Trace

from test_program_spans import _Cell, span

CELL = "mellum2-12b-a2.5b-instruct.serve-mixedlen-32"
CONFIG = "mellum2-12b-a2.5b-instruct"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = types.SimpleNamespace(hbm_bytes_per_s=819e9, bf16_flops=197e12)
NEW = ("moe_bank_roofline", "decode_rows_past_window")


def test_published_is_the_catalog_row_and_the_cut_is_depth_and_context():
    data = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", CONFIG + ".json"))
    cut = {"num_hidden_layers": 8, "max_position_embeddings": 32768}
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"Mellum2-12B-A2.5B-Instruct"' in ln)
        assert data["published"] == {**row["config"],
                                     "num_local_experts": 64}
        assert data["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert data[key] == cut.get(key, value), key
    assert data["reduced"] == list(cut)
    assert data["roles"]["serve"]["model"] == cut
    cell = manifest.Cell(CELL)
    assert cell.model == {**data["published"], **cut}
    for key in manifest.WIDTH_KEYS:
        assert data[key] == data["published"][key], key
    assert (data["hidden_size"], data["num_attention_heads"],
            data["num_key_value_heads"], data["head_dim"],
            data["moe_intermediate_size"], data["num_experts"],
            data["num_experts_per_tok"], data["sliding_window"],
            data["vocab_size"]) == (2304, 32, 4, 128, 896, 64, 8, 1024,
                                    98304)
    for said in ("4 chips", "2 + 2 + 2 + 1", "EVERY expert",
                 "eight-layer stages"):
        assert said in data["deployment"], said
    role = cell.role
    assert "decode_quantum" not in json.dumps(data["roles"])
    assert role["scheduler"] == {"max_admissions_per_tick": 1}
    assert role["program_options"] == {}
    engine = role["engine"]
    assert (engine["split_prefill_chunk"], engine["prefill_bucket"]) == (
        512, 64)
    assert engine["ragged"] == {
        "max_tracked_sequences": 32, "max_ragged_batch_size": 32,
        "memory_config_blocks": 10240, "block_size": 32}
    entry = next(w for w in cell.manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "mixedlen-closed-32", 1) and len(entry["why"]) <= 200


def test_the_bytes_are_the_issues():
    """Parameters and pools from the shapes the program builds (no array is
    made): 7.59 GB of weights, 1.34 GB of full-kind KV, 0.62 GB of window
    kind - the issue's 9.55 GB before activations."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.ragged import WindowKind

    cell = manifest.Cell(CELL)
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    module = cell.family.module()
    params = jax.eval_shape(
        lambda k: module.init(cfg, k, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    size = lambda tree: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                            for a in jax.tree.leaves(tree))
    assert size({k: params[k] for k in ("embed", "lm_head")}) \
        == 2 * 98304 * 2304 * 2
    assert size(params["layers"]["moe"]["w_up"]) == 8 * 64 * 2304 * 896 * 2
    assert size(params) == pytest.approx(7.59e9, rel=2e-3)
    ragged = cell.role["engine"]["ragged"]
    kind = WindowKind.sized("window", cfg.sliding_window,
                            ragged["max_tracked_sequences"],
                            cell.role["engine"]["split_prefill_chunk"],
                            ragged["block_size"])
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, ragged["memory_config_blocks"], ragged["block_size"],
        window_blocks={"window": kind.num_blocks}))
    assert cache["k"].shape == (2, 10240, 4, 32, 128)
    assert cache["k_window"].shape == (6, 1569, 4, 32, 128)
    assert size({k: cache[k] for k in "kv"}) == pytest.approx(1.342e9,
                                                              rel=1e-3)
    assert size({k: cache[k + "_window"] for k in "kv"}) == pytest.approx(
        0.617e9, rel=1e-3)
    assert 9.5e9 < size(params) + size(cache) < 9.6e9
    assert costs_window.layers_of(cell.model) == {"full": 2, "window": 6}
    assert costs_window.kv_bytes_per_token_layer(cell.model) == 2048


def test_the_traffic_draws_both_regimes_from_one_table():
    """A quarter of the sizes never leave the 1024 window (prompt + answer),
    a quarter are 8-24 windows long, and the full kind's pool holds the 32
    longest at once."""
    traffic = manifest.Cell(CELL).traffic
    assert (traffic["kind"], traffic["role"], traffic["clients"]) == (
        "closed_loop", "serve", 32)
    assert traffic["prompt_tokens"] == {"min": 256, "max": 24576,
                                        "spacing": "log"}
    assert traffic["answer_tokens"] == {"min": 32, "max": 512,
                                        "spacing": "log"}
    assert traffic["probes"] == [[512, 8], [3072, 8], [12288, 8]]
    table = sizes.size_table(traffic)
    totals = sorted((p + a for p, a in table), reverse=True)
    assert len(table) == 64
    assert sum(t <= 1024 for t in totals) == 17
    assert sum(t >= 8 * 1024 for t in totals) == 16 and totals[0] < 24 * 1024
    assert sum(-(-t // 32) for t in totals[:32]) == 9854 <= 10240 - 1


# -- the readers, on hand-made spans and operations -------------------------- #
def synthetic(named=True):
    """Two ticks of a mixed program: 17 live decode rows, 11 of them past the
    window, and a 512-row chunk; the eight layers' banks take 10 ms."""
    cell = manifest.Cell(CELL)
    spans, ops = [], []
    for t0 in (0, 50_000_000):
        args = dict(moe_rows_routed=529 * 8, moe_rows_computed=7936,
                    rows_past_window=11) if named else {}
        spans += [span("sched_tick", t0, t0 + 45_000_000),
                  span("decode_step", t0 + 10, t0 + 44_000_000, batch=17,
                       chunk_tokens=512, **args)]
        body = "jit(decode_chunk)/kv_write/while/body/while/body/"
        ms = lambda a, b: (t0 + int(a * 1e6), t0 + int(b * 1e6))
        ops += [(Op("paged_prefill.2", *ms(1, 5), "mosaic"),
                 body + "attn/attn_full/pallas_call"),
                (Op("moe_grouped_matmul.9" if named else "fusion.9",
                    *ms(5, 15), "mosaic"),
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
    """The bank's floor is counted by ``costs_moe.bank_floor_s`` - the
    function the older cells' shares use - at ONE expert's width (896, not
    the 7168 no layer has): 529 rows reach all 64 experts, 0.793 GB a layer,
    0.97 ms at the HBM peak; eight layers, two ticks, over 20 ms of
    kernel."""
    ctx = synthetic()
    m = ctx["cell"].model
    one = {**m, "intermediate_size": 896}
    assert costs_moe.experts_touched(one, 529) == pytest.approx(64, rel=1e-6)
    assert costs_moe.bank_bytes(one, 529) == pytest.approx(
        64 * 3 * 2304 * 896 * 2, rel=1e-6)
    floor = costs_moe.bank_floor_s(one, 529 * 8, PEAKS)
    assert floor == pytest.approx(0.793e9 / 819e9, rel=2e-3)
    got = read(ctx, "moe_bank_roofline")
    assert got == pytest.approx(100 * 8 * 2 * floor / 20e-3) and got < 100
    # the published dense width would have counted eight times as much
    assert costs_moe.bank_floor_s(m, 529 * 8, PEAKS) == pytest.approx(
        8 * floor)
    assert read(ctx, "decode_rows_past_window") == 11


@pytest.mark.parametrize("name", NEW)
def test_a_program_that_names_nothing_reports_nothing(name):
    """A program without the span arguments (the parent's cannot run the
    cell at all; a family with one kind of KV state says no
    ``rows_past_window``) or without the kernel: every new reader returns
    None and the line leaves the metric out."""
    assert read(synthetic(named=False), name) is None


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
    assert mine["moe_bank_roofline"]["layer"] == "Kernels"
    assert mine["moe_bank_roofline"]["source"] == "device_trace"
    assert mine["decode_rows_past_window"]["source"] == "program_counter"
    # the rate spread 0.93 % in one set of six (half its bound is 0.75): the
    # cell reports the tail alone, as Nemotron's, Brumby's and Solar's do -
    # so it joins the lists that move the tail and no other
    assert ends == ["itl_p99_ms", "setup_s"]
    for name in ("serve_attn_share", "serve_attn_window_share",
                 "serve_attn_full_share", "serve_ffn_share",
                 "serve_kv_write_share", "mixed_kv_decode_roofline",
                 "decode_step_ms_p50", "serve_chunk_tick_share",
                 "serve_mosaic_share"):
        assert name in mine, name
    # dead readers, the share that reads the dense width, and what moves
    # the rate this cell does not report
    for name in ("prefill_chunk_ms_p50", "sched_host_ms_p50",
                 "moe_experts_roofline", "mixed_kv_prefill_roofline",
                 "window_blocks_released_per_tick", "kv_resident_share",
                 "moe_padded_row_share", "serve_moe_router_share"):
        assert name not in mine, name
    names = [m["name"] for m in cell.manifest["per_layer"]]
    assert names[-2:] == list(NEW)
    assert cell.manifest["workloads"][-1]["name"] == CELL
    assert cell.manifest["configs"][-1]["name"] == CONFIG
    four = [w["name"] for w in cell.manifest["workloads"] if w["chips"] == 4]
    assert CELL not in four and len(four) <= len(
        cell.manifest["workloads"]) // 4


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
    assert all(0 < ln["decode_rows"] < ln["rows"] for ln in held)
    assert not any(ln.get("compiles_in_window") for ln in lines)
    assert result["rehearsal"]["decode_rows_past_window"]["value"] > 0
