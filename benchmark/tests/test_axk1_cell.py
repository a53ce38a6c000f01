"""What ISSUE 47 added for ``a.x-k1.serve-longctx``: the configuration file
against the catalog row, the bytes the issue reckoned, ``costs_mla`` on
hand-computed numbers, and the five new readers on hand-made spans and
operations (no roofline over 100; a program without a latent cache reports
nothing). (Program against reference: ``tests/test_axk1.py``, tier-1.)"""

import json
import os
import types

import jax
import pytest

from benchmark.harness import costs_mla, manifest
from benchmark.harness import program_spans as ps
from benchmark.harness.trace import Op, Trace

from test_program_spans import _Cell, span

CELL = "a.x-k1.serve-longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = types.SimpleNamespace(hbm_bytes_per_s=819e9, bf16_flops=197e12)
NEW = ("serve_attn_latent_share", "serve_mla_absorb_share",
       "mla_decode_roofline", "mla_prefill_roofline",
       "latent_kv_resident_share")


def test_published_is_the_catalog_row_and_the_top_level_is_what_runs():
    data = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "a.x-k1.json"))
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"A.X-K1"' in ln)
        # the one key under 'published' this benchmark adds: the router's
        # width under the name the harness reads
        assert data["published"] == {**row["config"],
                                     "num_local_experts": 192}
        assert data["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in data["reduced"]:
                assert data[key] == value, key
    cut = {"num_hidden_layers": 5, "num_experts": 12,
           "max_position_embeddings": 32768}
    assert data["reduced"] == list(cut)
    assert data["roles"]["serve"]["model"] == cut
    cell = manifest.Cell(CELL)
    assert cell.model == {**data["published"], **cut}
    assert {k: data[k] for k in cell.model} == cell.model
    for key in manifest.WIDTH_KEYS:
        if key in data["published"]:
            assert data[key] == data["published"][key], key
    # inside the guide's floors: the dense layer and four sparse ones, 8
    # experts at least, the vocabulary whole
    assert data["num_experts"] >= 8 and data["num_hidden_layers"] >= 4
    assert data["n_routed_experts"] == data["num_local_experts"] == 192
    for said in ("16 chips share each layer", "12 of 192", "four sparse"):
        assert said in data["deployment"]
    for key in ("num_local_experts", "topk_method", "expert_width",
                "attention", "rope", "vocab_size", "weights"):
        assert key in data["assumed"], key
    entry = next(m for m in cell.manifest["workloads"] if m["name"] == CELL)
    assert entry["traffic"] == "longctx-closed-16" and entry["chips"] == 1
    assert cell.traffic["probes"] == [[1024, 8], [6144, 8], [12288, 8]]


def test_the_bytes_are_the_issues():
    """ISSUE 47's arithmetic: attention 101.1 M a layer, an expert 44.0 M, a
    sparse layer 675.0 M, the dense layer 497.5 M, 11.1 GB of weights; the
    cache 1 152 B a token a layer against the 40 960 B of 64 heads' keys and
    values, 1 280 B as the pool lays it; the pool 2.58 GB."""
    cell = manifest.Cell(CELL)
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    shapes = jax.eval_shape(lambda k: cell.family.module().init(cfg, k),
                            jax.random.PRNGKey(0))
    count = lambda tree: sum(int(x.size) for x in jax.tree.leaves(tree))
    norms = 7168 * 2 + 1536 + 512
    sparse = {k: v for k, v in shapes["layers"].items() if k != "moe"}
    assert count(sparse) // 4 - norms == 101_122_048          # attention
    moe = shapes["layers"]["moe"]
    assert moe["w_gate"].shape == (4, 12, 7168, 2048)
    assert count(moe) // 4 == 12 * 44_040_192 + 44_040_192 + 7168 * 192
    assert round(count(shapes["layers"]) / 4 / 1e6, 1) == 675.0
    assert round(count(shapes["dense_layers"]) / 1e6, 1) == 497.5
    assert round(2 * count(shapes) / 1e9, 1) == 11.1
    m = cell.model
    assert costs_mla.latent_row_bytes(m) == 1152
    assert costs_mla.full_kv_bytes_per_token_layer(m) == 40960
    ragged = cell.role["engine"]["ragged"]
    pool = jax.eval_shape(lambda: cell.family.module().init_paged_cache(
        cfg, ragged["memory_config_blocks"], ragged["block_size"]))["latent"]
    assert pool.shape == (5, 3152, 1, 128, 640)
    assert round(pool.size * 2 / 1e9, 2) == 2.58
    # the sixteen longest requests at once and a block each
    assert ragged["memory_config_blocks"] * ragged["block_size"] \
        >= 16 * (24576 + 512 + ragged["block_size"])


def test_costs_mla_on_hand_computed_numbers():
    m = manifest.Cell(CELL).model
    assert costs_mla.decode_kv_bytes(m, 1000) == 5 * 1000 * 1152
    assert costs_mla.chunk_keys(100, 4) == 101 + 102 + 103 + 104
    assert costs_mla.attn_flops_per_pair(m) == 2 * (576 + 512)
    assert costs_mla.chunk_attn_flops(m, 12288, 512) == \
        5 * 64 * 2176 * (512 * 12288 + 512 * 513 // 2)
    # the issue's count: 4.3 TFLOP for a 512-row chunk at a 12 k context
    assert round(costs_mla.chunk_attn_flops(m, 12000, 512) / 1e12, 1) == 4.4


# -- the readers, on hand-made spans and operations -------------------------- #
def synthetic(ctx_tokens=10_000, latent=True):
    """Two ticks of a mixed program: 12 decode rows at ``ctx_tokens`` each
    and a 512-token chunk at that offset, with what one layer reads on the
    span; the walks take 1200 + 300 ns, the absorb matmuls 100 ns and the
    dense FFN 200 ns of each tick's 4000."""
    model = manifest.Cell(CELL).model
    spans, ops = [], []
    for t0 in (0, 5000):
        args = dict(kv_tokens_latent=12 * (ctx_tokens + 1),
                    chunk_kv_tokens_latent=ctx_tokens + 512) if latent else {}
        tick = dict(latent_blocks_live=1500 + (t0 > 0) * 100,
                    latent_blocks=3151) if latent else {}
        spans += [span("sched_tick", t0, t0 + 4500, **tick),
                  span("decode_step", t0 + 10, t0 + 4400, batch=12,
                       chunk_tokens=512, chunk_ctx=ctx_tokens, **args)]
        where = "jit(decode_chunk)/kv_write/while/body/attn/attn_latent/"
        ops += [(Op("paged_prefill.3", t0 + 100, t0 + 1300, "mosaic"),
                 where + "pallas_call"),
                (Op("paged_decode.3", t0 + 1300, t0 + 1600, "mosaic"),
                 where + "pallas_call"),
                (Op("fusion.5", t0 + 1600, t0 + 1700, "xla"),
                 where + "mla_absorb/dot_general"),
                (Op("fusion.6", t0 + 1700, t0 + 1900, "xla"),
                 "jit(decode_chunk)/ffn/dense_ffn/dot_general"),
                (Op("fusion.9", t0 + 1900, t0 + 4100, "xla"),
                 "jit(decode_chunk)/kv_write/while/body/moe_experts/dot")]
    plane = "/device:TPU:0"
    trace = Trace({plane: [op for op, _ in ops]}, {plane: []},
                  [("window", 0, 10000)])
    return {"cell": _Cell("synthetic", model=model), "trace": trace,
            "peaks": PEAKS, "program_spans": ps.Program(ps.link(spans),
                                                        {plane: ops})}


def read(ctx, name):
    definition = manifest.metric_definition(name)
    return manifest.reader(definition["reader"]).read(
        ctx, **definition.get("params", {}))


def test_the_new_readers_on_hand_made_spans():
    ctx = synthetic()
    assert read(ctx, "serve_attn_latent_share") == pytest.approx(
        100 * 1500 / 4000)
    assert read(ctx, "serve_mla_absorb_share") == pytest.approx(
        100 * 100 / 4000)
    assert read(ctx, "latent_kv_resident_share") == pytest.approx(
        100 * 1550 / 3151)
    m = ctx["cell"].model
    bytes_ = 2 * costs_mla.decode_kv_bytes(m, 12 * 10_001)
    assert read(ctx, "mla_decode_roofline") == pytest.approx(
        100 * bytes_ / 819e9 / (2 * 300e-9))
    flops = 2 * costs_mla.chunk_attn_flops(m, 10_000, 512)
    assert read(ctx, "mla_prefill_roofline") == pytest.approx(
        100 * flops / 197e12 / (2 * 1200e-9))
    # the accepted scope readers book the new scopes to attn and ffn
    assert read(ctx, "serve_attn_share") == pytest.approx(100 * 1600 / 4000)
    assert read(ctx, "serve_ffn_share") == pytest.approx(
        100 * (200 + 2200) / 4000)      # the experts are ffn's too


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_a_latent_cache_reports_nothing(name):
    """The parent's program has neither the span arguments nor the scopes:
    every new reader returns None and the line leaves the metric out."""
    ctx = synthetic(latent=False)
    for plane, ops in ctx["program_spans"].ops.items():
        ctx["program_spans"].ops[plane] = [
            (op, name_.replace("attn_latent/", "").replace("mla_absorb/", "")
             .replace("dense_ffn/", "")) for op, name_ in ops]
    assert read(ctx, name) is None


def test_the_metrics_are_in_the_manifest_under_their_layers():
    cell = manifest.Cell(CELL)
    mine = {m["name"]: m for m in cell.metrics("per_layer")}
    assert set(NEW) <= set(mine)
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
    # ``itl_p99_ms`` is NOT this cell's: its top 1 % is ~100 samples of ~8
    # ticks, and one host stall of 0.1 s (0-8 a window, PERF.md section 7 m)
    # moves it by 0.55 ms - five sets of six spread by 0.36-1.74 % where a
    # new cell is admitted under 1 % (PERF.md section 6, PR 47). So every
    # metric the cell reports moves the rate
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "serve_tokens_per_s", "setup_s"]
    assert {m["moves"] for m in mine.values()} == {"serve_tokens_per_s"}
    for name in ("moe_experts_roofline", "decode_hbm_share",
                 "paged_decode_roofline", "serve_attn_window_share",
                 "kv_resident_share", "prefill_chunk_ms_p50",
                 "serve_attn_share", "decode_step_ms_p50"):
        assert name not in mine
