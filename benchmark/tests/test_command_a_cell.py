"""What ISSUE 42 added for ``command-a-plus-05-2026.serve-longctx``: the
configuration file against the catalog row, the bytes the issue reckoned,
``costs_window`` on hand-computed numbers, and the six new readers on
hand-made spans and operations (a stack of full layers alone keeps 100 % of
one table resident; no roofline over 100). (Program against reference:
``tests/test_cohere2_moe.py``, the manager: ``tests/test_kv_kinds.py``,
tier-1.)"""

import json
import os
import types

import jax
import pytest

from benchmark.harness import costs, costs_window, manifest
from benchmark.harness import program_spans as ps
from benchmark.harness.trace import Op, Trace

from test_program_spans import _Cell, span

CELL = "command-a-plus-05-2026.serve-longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = types.SimpleNamespace(hbm_bytes_per_s=819e9, bf16_flops=197e12)
NEW = ("serve_attn_window_share", "serve_attn_full_share",
       "kv_resident_share", "window_blocks_released_per_tick",
       "mixed_kv_decode_roofline", "mixed_kv_prefill_roofline")


def test_published_is_the_catalog_row_and_the_top_level_is_what_runs():
    data = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "command-a-plus-05-2026.json"))
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"command-a-plus-05-2026"' in ln)
        # the one key this benchmark adds: the router's width
        assert data["published"] == {**row["config"],
                                     "num_local_experts": 128}
        assert data["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in data["reduced"]:
                assert data[key] == value, key
    cut = {"num_hidden_layers": 4, "num_experts": 16,
           "max_position_embeddings": 32768}
    assert data["reduced"] == list(cut)
    assert data["roles"]["serve"]["model"] == cut
    cell = manifest.Cell(CELL)
    assert cell.model == {**data["published"], **cut}
    assert {k: data[k] for k in data["published"]} == cell.model
    for key in manifest.WIDTH_KEYS:
        assert data[key] == data["published"][key], key
    # inside the guide's floors: a whole period, 8 experts, the vocabulary
    assert data["num_experts"] >= 8 and data["num_hidden_layers"] >= 4
    assert costs_window.layers_of(cell.model) == {"full": 1, "window": 3}
    for said in ("8 chips share each layer", "4 of 32 layers", "64 chips"):
        assert said in data["deployment"]
    for key in ("num_local_experts", "shared_experts", "router", "window",
                "vision_tower", "experts_held", "vocabulary", "weights"):
        assert key in data["assumed"], key
    entry = next(m for m in cell.manifest["workloads"] if m["name"] == CELL)
    assert entry["traffic"] == "longctx-closed-16" and entry["chips"] == 1
    t = cell.traffic
    assert (t["clients"], t["size_table"], t["warmup_ticks"],
            t["trace_units"]) == (16, 64, 32, 64)
    assert t["probes"] == [[1024, 8], [6144, 8], [12288, 8]]


def test_the_bytes_are_the_issues():
    """ISSUE 42's arithmetic: a layer outside its routed experts 344.46 M,
    an expert 50.33 M, 11.35 GB of weights, 4096 B of cache a token a layer,
    pools of 1.64 and 0.91 GB."""
    from deepspeed_tpu.inference.ragged import WindowKind

    cell = manifest.Cell(CELL)
    m = cell.model
    outside = costs.attention_params(m) + costs.router_params(m) \
        + m["num_shared_experts"] * costs.ffn_params(m) + m["hidden_size"]
    assert outside == 344_461_312 and costs.ffn_params(m) == 50_331_648
    weights = 2 * (4 * (outside + 16 * costs.ffn_params(m))
                   + costs.head_params(m) + m["hidden_size"])
    assert weights == pytest.approx(11.35e9, rel=0.001)
    cfg = cell.family.build_cfg(m, **cell.role["program_options"])
    module = cell.family.module()
    shapes = jax.eval_shape(lambda k: module.init(cfg, k),
                            jax.random.PRNGKey(0))
    assert 2 * sum(s.size for s in jax.tree.leaves(shapes)) == weights
    assert costs_window.kv_bytes_per_token_layer(m) == 4096
    engine = cell.role["engine"]
    ragged = engine["ragged"]
    kind = WindowKind.sized("window", m["sliding_window"],
                            ragged["max_tracked_sequences"],
                            engine["split_prefill_chunk"],
                            ragged["block_size"])
    assert kind.blocks_per_seq == 145
    full = ragged["memory_config_blocks"] * 32 * 4096
    window = kind.num_blocks * 32 * 4096 * 3
    assert full == pytest.approx(1.64e9, rel=0.005)
    assert window == pytest.approx(0.91e9, rel=0.005)
    # sixteen of the longest requests at once: never preempted
    from benchmark.harness.sizes import size_table

    longest = max(p + a for p, a in size_table(cell.traffic))
    assert longest <= m["max_position_embeddings"]
    assert 16 * (-(-longest // 32) + 1) < ragged["memory_config_blocks"]
    # one table would need four layers of the full kind's pool: too much
    assert 4 * full + weights > 16.9e9 > full + window + weights


def test_costs_window_on_hand_computed_numbers():
    m = manifest.Cell(CELL).model
    # a decode row at 16 k: 16 k tokens in the full layer, 4 k in each of 3
    assert costs_window.decode_kv_bytes(m, 16384, 4096) == \
        (3 * 4096 + 16384) * 4096
    assert costs_window.chunk_keys(0, 4) == 1 + 2 + 3 + 4
    assert costs_window.chunk_keys(10, 3) == 11 + 12 + 13
    assert costs_window.chunk_keys(10, 3, window=12) == 11 + 12 + 12
    assert costs_window.chunk_keys(8192, 512, window=4096) == 512 * 4096
    pairs = costs_window.chunk_keys(8192, 512) + 3 * 512 * 4096
    assert costs_window.chunk_attn_flops(m, 8192, 512) == \
        4.0 * 128 * 128 * pairs
    assert costs_window.resident_share(m, 16384, 4096) == \
        pytest.approx((16384 + 3 * 4096) / (4 * 16384))
    only_full = {**m, "layer_types": ["full_attention"] * 4}
    assert costs_window.resident_share(only_full, 16384, 4096) == 1.0


# -- the readers, on hand-made spans and operations -------------------------- #
def synthetic(ctx_tokens=10_000, window=4096, kinds=True):
    """Two ticks of a mixed program: 12 decode rows at ``ctx_tokens`` each
    and a 512-token chunk at that offset, with what one layer of each kind
    reads on the span; the window layers' walks take 900 ns and the full
    layer's 700 ns of each tick's 4000."""
    model = manifest.Cell(CELL).model
    spans, ops = [], []
    for t0 in (0, 5000):
        args = dict(
            kv_tokens_full=12 * (ctx_tokens + 1),
            kv_tokens_window=12 * min(ctx_tokens + 1, window),
            chunk_kv_tokens_full=ctx_tokens + 512,
            chunk_kv_tokens_window=min(ctx_tokens + 512,
                                       window - 1 + 512)) if kinds else {}
        spans += [span("sched_tick", t0, t0 + 4500,
                       **({"window_blocks_released": 16 + (t0 > 0)}
                          if kinds else {})),
                  span("decode_step", t0 + 10, t0 + 4400, batch=12,
                       chunk_tokens=512, chunk_ctx=ctx_tokens, **args)]
        where = "jit(decode_chunk)/kv_write/while/body/attn/"
        ops += [(Op("paged_prefill.3", t0 + 100, t0 + 700, "mosaic"),
                 where + "attn_window/pallas_call"),
                (Op("paged_decode.3", t0 + 700, t0 + 1000, "mosaic"),
                 where + "attn_window/pallas_call"),
                (Op("paged_prefill.4", t0 + 1000, t0 + 1500, "mosaic"),
                 where + "attn_full/pallas_call"),
                (Op("paged_decode.4", t0 + 1500, t0 + 1700, "mosaic"),
                 where + "attn_full/pallas_call"),
                (Op("fusion.9", t0 + 1700, t0 + 4100, "xla"),
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
    assert read(ctx, "serve_attn_window_share") == pytest.approx(
        100 * 900 / 4000)
    assert read(ctx, "serve_attn_full_share") == pytest.approx(
        100 * 700 / 4000)
    assert read(ctx, "window_blocks_released_per_tick") == 16.5
    full = 12 * 10_001 + 10_512
    window = 12 * 4096 + 4095 + 512
    assert read(ctx, "kv_resident_share") == pytest.approx(
        100 * (full + 3 * window) / (4 * full))
    m = ctx["cell"].model
    bytes_ = 2 * costs_window.decode_kv_bytes(m, 12 * 10_001, 12 * 4096)
    assert read(ctx, "mixed_kv_decode_roofline") == pytest.approx(
        100 * bytes_ / 819e9 / (2 * 500e-9))
    flops = 2 * costs_window.chunk_attn_flops(m, 10_000, 512)
    assert read(ctx, "mixed_kv_prefill_roofline") == pytest.approx(
        100 * flops / 197e12 / (2 * 1100e-9))


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_kinds_reports_nothing(name):
    """The parent's program has neither the span arguments nor the scopes:
    every new reader returns None and the line leaves the metric out."""
    ctx = synthetic(kinds=False)
    for plane, ops in ctx["program_spans"].ops.items():
        ctx["program_spans"].ops[plane] = [
            (op, name_.replace("attn_window/", "").replace("attn_full/", ""))
            for op, name_ in ops]
    assert read(ctx, name) is None
