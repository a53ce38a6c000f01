"""What ISSUE 64 added for ``zaya1-8b.serve-reason-64``: the configuration
file against the catalog row, the bytes the issue reckoned, the traffic file
left as it was, the new readers on hand-made spans and operations (no
roofline over 100; a program that names nothing reports nothing), the
manifest's entries by MEMBERSHIP (no count of cells, configurations or
metrics, and no "last entry"), and the cell rehearsed through the real
command line. (Program against reference in float32: ``tests/test_zaya.py``,
tier-1.)"""

import hashlib
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.harness import costs, costs_cca, manifest, sizes
from benchmark.harness import program_spans as ps
from benchmark.harness.trace import Op, Trace

from test_program_spans import _Cell, span

CELL = "zaya1-8b.serve-reason-64"
CONFIG = "zaya1-8b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = types.SimpleNamespace(hbm_bytes_per_s=819e9, bf16_flops=197e12)
NEW = ("serve_cca_mix_share", "cca_mix_roofline",
       "serve_moe_router_share.itl")


def test_published_is_the_catalog_row_and_the_cut_is_depth_and_context():
    data = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", CONFIG + ".json"))
    cut = {"num_hidden_layers": 20, "max_position_embeddings": 4096}
    added = {"intermediate_size": 2048, "num_local_experts": 16}
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"ZAYA1-8B"' in ln)
        assert data["published"] == {**row["config"], **added}
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
            data["num_experts_per_tok"], data["router_hidden_size"],
            data["cca_time0"], data["cca_time1"],
            data["partial_rotary_factor"], data["vocab_size"]) == (
        2048, 8, 2, 128, 2048, 16, 1, 256, 2, 2, 0.5, 262272)
    for said in ("2 chips", "two pipeline stages of 20 layers",
                 "FIRST stage", "16 experts", "whole tied vocabulary"):
        assert said in data["deployment"], said
    role = cell.role
    # (no preemption: a pool too small for the loop FAILS the run, below)
    assert role["scheduler"] == {"max_admissions_per_tick": 1,
                                 "preempt": False}
    assert role["program_options"] == {}
    engine = role["engine"]
    assert (engine["split_prefill_chunk"], engine["prefill_bucket"]) == (
        512, 64)
    assert engine["ragged"] == {
        "max_tracked_sequences": 64, "max_ragged_batch_size": 64,
        "memory_config_blocks": 2560, "block_size": 64}
    entry = next(w for w in cell.manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "reason-closed-64", 1) and len(entry["why"]) <= 200
    config = next(c for c in cell.manifest["configs"] if c["name"] == CONFIG)
    assert config["source"] == data["source"]
    assert config["reduced"] == list(cut)


def test_the_bytes_are_the_issues():
    """Parameters and pools from the shapes the program builds (no array is
    made): 9.38 GB of weights, 20 480 B of K and V a token, 3.36 GB of
    pages, 10.6 MB of tails - and the harness's counts of the same model."""
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
    assert size(params["embed"]) == 262272 * 2048 * 2
    assert size(params["layers"]["moe"]["w_up"]) == 20 * 16 * 2048 * 2048 * 2
    # (the routers float32: 26 MB more than 2 bytes a parameter)
    assert size(params) == pytest.approx(9.38e9, rel=3e-3)
    ragged = cell.role["engine"]["ragged"]
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, ragged["memory_config_blocks"], ragged["block_size"],
        slots=ragged["max_tracked_sequences"]))
    assert cache["k"].shape == (20, 2560, 2, 64, 128)
    assert cache["tail"].shape == (20, 65, 16, 256)
    assert size({k: cache[k] for k in "kv"}) == 2560 * 64 * 20480
    assert size(cache["tail"]) == pytest.approx(10.6e6, rel=1e-2)
    m = cell.model
    assert costs.kv_bytes_per_token(m) == 20480 and costs.head_dim(m) == 128
    assert costs.attention_params(m) == 5242880
    assert costs.ffn_params(m) == 3 * 2048 * 2048
    assert costs.experts(m) == 16 and costs.experts_per_token(m) == 1
    # a decode step of 64 rows reads 15.7 of a layer's 16 experts
    step = costs.weight_bytes_read_per_decode_step(m, 64)
    assert step == pytest.approx(20 * (5.24e6 + 15.74 * 12.58e6) * 2
                                 + 1.074e9, rel=5e-3)
    assert costs_cca.latent(m) == 1280
    assert costs_cca.tail_numbers(m) == 2688
    assert costs_cca.conv_params(m) == 3 * 1280 + 2 * 10 * 128 * 128 + 1280
    # a 64-row decode call: 0.39 MB of rows, 0.69 MB of tails, 0.67 MB of
    # convolution weights a layer
    assert costs_cca.call_floor_bytes(m, 64, 64) == pytest.approx(
        2 * (64 * 3072 + 2 * 64 * 2688 + 332800))


def test_the_traffic_file_is_the_one_nemotrons_cell_runs():
    """``traffic/reason-closed-64.json`` byte for byte as ISSUE 50 left it,
    and what the pool is sized by: its 64 sizes at once are 126 062 tokens,
    the longest 3 519."""
    path = os.path.join(manifest.BENCH_DIR, "traffic",
                        "reason-closed-64.json")
    assert hashlib.sha256(open(path, "rb").read()).hexdigest()[:16] \
        == "491f3b59f2963e95"
    traffic = manifest.Cell(CELL).traffic
    assert (traffic["kind"], traffic["role"], traffic["clients"]) == (
        "closed_loop", "serve", 64)
    assert traffic["probes"] == [[256, 8], [2048, 8], [640, 96]]
    totals = [p + a for p, a in sizes.size_table(traffic)]
    assert (sum(totals), max(totals)) == (126062, 3519)
    assert sum(-(-t // 64) for t in totals) == 2004 <= 2560 - 1


def test_the_pool_holds_the_loops_own_demand_with_room():
    """The loop's sizes, and which client sends which when, are the traffic
    file's alone, and a tick moves every sequence by a token or a chunk: the
    blocks the 64 clients hold tick by tick are the same in every run. Walked
    here for 30 000 ticks (a window ends near tick 3 000), one chunk of the
    oldest prompt a tick: never more than three quarters of the pool, so
    the role can turn preemption OFF - a run that ran out of blocks would
    fail, not park a sequence quietly."""
    cell = manifest.Cell(CELL)
    ragged = cell.role["engine"]["ragged"]
    block, chunk = ragged["block_size"], cell.role["engine"][
        "split_prefill_chunk"]
    plan = sizes.ClosedLoopPlan(cell.traffic, 0, 1000)
    sent, live, queue, peak = [0] * plan.clients, {}, [], 0
    for _ in range(30000):
        for k in range(plan.clients):
            if k not in live:
                live[k] = [*plan.sizes(k, sent[k]), 0, 0]
                sent[k] += 1
                queue.append(k)
        if queue:       # [prompt, answer, prefilled, generated]
            s = live[queue[0]]
            s[2] = min(s[0], s[2] + chunk)
            if s[2] == s[0]:
                queue.pop(0)
        for k, s in list(live.items()):
            if s[2] == s[0]:
                s[3] += 1
                if s[3] > s[1]:
                    del live[k]
        peak = max(peak, sum(-(-(s[2] + s[3] + 1) // block)
                             for s in live.values()))
    assert 1500 < peak <= 0.75 * (ragged["memory_config_blocks"] - 1), peak


# -- the readers, on hand-made spans and operations -------------------------- #
def synthetic(named=True):
    """Two ticks of a mixed program: 64 live decode rows and a 512-row
    chunk; the twenty layers' mixing takes 4 ms of a tick's 20."""
    cell = manifest.Cell(CELL)
    spans, ops = [], []
    for t0 in (0, 50_000_000):
        args = dict(cca_rows=576, cca_tail_rows=65, moe_rows_routed=542,
                    moe_rows_skipped=34, moe_rows_computed=1024) \
            if named else {}
        spans += [span("sched_tick", t0, t0 + 45_000_000),
                  span("decode_step", t0 + 10, t0 + 44_000_000, batch=64,
                       chunk_tokens=512, rows=576, **args)]
        body = "jit(decode_chunk)/kv_write/while/body/"
        ms = lambda a, b: (t0 + int(a * 1e6), t0 + int(b * 1e6))
        ops += [(Op("paged_decode.2", *ms(1, 5), "mosaic"),
                 body + "attn/pallas_call"),
                (Op("fusion.7", *ms(5, 9), "fusion"),
                 body + ("attn/cca_mix/mul" if named else "attn/mul")),
                (Op("fusion.8", *ms(9, 10), "fusion"),
                 body + ("moe_router/dot" if named else "dot")),
                (Op("moe_grouped_matmul.9", *ms(10, 21), "mosaic"),
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
    """The mixing's share is its scope's own time over busy time; its floor
    is ``costs_cca.call_floor_bytes`` of the rows and the tails the span
    says, times the layers, over the HBM peak; the router's share is its
    scope's own time over busy time."""
    ctx = synthetic()
    m = ctx["cell"].model
    assert read(ctx, "serve_cca_mix_share") == pytest.approx(100 * 4 / 20)
    floor = 20 * costs_cca.call_floor_bytes(m, 576, 65) / 819e9
    assert floor == pytest.approx(20 * 4.90e6 / 819e9, rel=1e-2)
    got = read(ctx, "cca_mix_roofline")
    assert got == pytest.approx(100 * 2 * floor / 8e-3) and got < 100
    assert read(ctx, "serve_moe_router_share.itl") == pytest.approx(
        100 * 1 / 20)


@pytest.mark.parametrize("name", NEW)
def test_a_program_that_names_nothing_reports_nothing(name):
    """A program without the span arguments or without the scope (the
    parent's cannot run the cell at all): every new reader returns None and
    the line leaves the metric out."""
    assert read(synthetic(named=False), name) is None


def test_the_metrics_are_in_the_manifest_under_their_layers():
    cell = manifest.Cell(CELL)
    mine = {m["name"]: m for m in cell.metrics("per_layer")}
    ends = [m["name"] for m in cell.metrics("end_to_end")]
    assert set(NEW) <= set(mine)
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "itl_p99_ms"
        assert set(mine[name]) == {"name", "unit", "better", "source",
                                   "layer", "moves", "workloads"}
    for m in mine.values():
        assert m["moves"] in ends, m
    assert mine["cca_mix_roofline"]["layer"] == "Kernels"
    assert mine["serve_cca_mix_share"]["source"] == "device_trace"
    assert mine["serve_moe_router_share.itl"]["source"] == "device_trace"
    assert "itl_p99_ms" in ends and "setup_s" in ends
    for name in ("serve_attn_share", "serve_ffn_share",
                 "serve_kv_write_share", "paged_decode_roofline",
                 "decode_step_ms_p50", "serve_chunk_tick_share",
                 "serve_mosaic_share", "decode_live_tile_share"):
        assert name in mine, name
    # by membership: the entries are there, wherever later PRs put theirs
    assert CELL in [w["name"] for w in cell.manifest["workloads"]]
    assert CONFIG in [c["name"] for c in cell.manifest["configs"]]
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
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    result = lines[-1]
    assert result["correct"] and result["failed"] == 0
    assert "metrics" not in result and "rehearsal" in result
    held = [ln for ln in lines if ln.get("phase") == "held"]
    assert len(held) == 3 and not any(ln["why_not"] for ln in held)
    assert all(0 < ln["decode_rows"] < ln["rows"] for ln in held)
    assert not any(ln.get("compiles_in_window") for ln in lines)
    assert result["rehearsal"]["serve_chunk_tick_share"]["value"] > 0
