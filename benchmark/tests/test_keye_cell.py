"""What ISSUE 38 added for ``keye-vl-2.0-30b-a3b.serve-longctx``: the
configuration file against the catalog row, the bytes the issue reckoned from
``costs_sparse`` and the family, the five new readers on hand-made spans and
operations (a call that selects everything reads 100; no roofline over 100),
and the cell through the real command line. (Program against reference:
``tests/test_keye.py``, tier-1.)"""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

from benchmark.harness import costs_sparse, manifest
from benchmark.harness import program_spans as ps
from benchmark.harness.trace import Op, Trace
from benchmark.reference import keye, keye_variants

from test_program_spans import OLDER, RECORDED, _Cell, _ctx, span

CELL = "keye-vl-2.0-30b-a3b.serve-longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = types.SimpleNamespace(hbm_bytes_per_s=819e9, bf16_flops=197e12)
NEW = {"serve_sparse_index_share", "serve_sparse_select_share",
       "sparse_kv_selected_share", "sparse_index_roofline",
       "sparse_attn_roofline"}


def config_file():
    return manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "keye-vl-2.0-30b-a3b.json"))


# -- the configuration -------------------------------------------------------- #
def test_published_is_the_catalog_row_and_the_top_level_is_what_runs():
    data = config_file()
    if os.path.exists(CATALOG):
        row = next(json.loads(ln) for ln in open(CATALOG)
                   if '"Keye-VL-2.0-30B-A3B"' in ln)
        assert data["published"] == row["config"]
        assert data["source"] == row["source_url"]
    cut = {"num_hidden_layers": 12, "num_experts": 16,
           "max_position_embeddings": 32768}
    assert data["reduced"] == list(cut)
    assert data["roles"]["serve"]["model"] == cut
    cell = manifest.Cell(CELL)
    assert cell.model == {**data["published"], **cut}
    assert {k: data[k] for k in data["published"]} == cell.model
    # no width differs; the router's width is a width
    for key in manifest.WIDTH_KEYS:
        if key in data["published"]:
            assert data[key] == data["published"][key], key
    assert data["num_local_experts"] == 128 and data["sa_config"]["topk"] == 2048
    # inside the guide's floors: 8 experts, 4 layers, the whole vocabulary
    assert data["num_experts"] >= 8 and data["num_hidden_layers"] >= 4
    assert data["vocab_size"] == data["published"]["vocab_size"]
    for said in ("8 chips share each layer", "12 of 48 layers"):
        assert said in data["deployment"]
    for key in ("vision_tower", "qk_norm", "indexer_input", "indexer_rope",
                "index_keys", "chunk_sizes", "index_weights", "ties",
                "experts_held", "weights"):
        assert key in data["assumed"], key


def test_the_family_builds_the_program_the_file_describes():
    from benchmark.families import keye as family

    cell = manifest.Cell(CELL)
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    assert (cfg.num_experts, cfg.experts_held, cfg.top_k) == (128, (0, 16), 8)
    assert (cfg.head_size, cfg.num_heads, cfg.num_kv_heads) == (128, 32, 4)
    assert cfg.intermediate_size == 768 and cfg.qk_norm and cfg.num_layers == 12
    sa = cfg.sparse_attention
    assert (sa.index_heads, sa.index_head_dim, sa.topk) == (16, 64, 2048)
    assert not cfg.drop_tokens and cfg.norm_topk_prob
    with pytest.raises(ValueError, match="norm_topk_prob"):
        cell.family.build_cfg(cell.model, drop_tokens=False,
                              norm_topk_prob=False)
    # the configuration's weights are the program's but for the QK gain
    module = family.module()
    toy = manifest.Cell(CELL, rehearsal=True)
    small = toy.family.build_cfg(toy.model, **toy.role["program_options"])
    mine = module.init(small, jax.random.PRNGKey(0))
    from deepspeed_tpu.models import mixtral
    theirs = mixtral.init(small, jax.random.PRNGKey(0))
    for name, leaf in mine["layers"].items():
        if name in ("q_norm", "k_norm"):
            assert float(leaf.min()) == float(leaf.max()) == family.QK_GAIN
        elif name != "moe":
            assert bool((leaf == theirs["layers"][name]).all()), name
    assert toy.model["sa_config"]["topk"] < toy.traffic["prompt_tokens"]["min"]


def test_the_bytes_are_the_issues():
    """ISSUE 38's arithmetic: a layer 21.40 M + 16 x 4.72 M parameters,
    weights 3.57 GB, 2176 B of cache a token a layer, a pool of 6.52 GB."""
    cell = manifest.Cell(CELL)
    p = costs_sparse.layer_params(cell.model)
    assert p["attention"] == 18_874_368 and p["indexer"] == 2_260_992
    assert p["router"] == 262_144 and p["expert"] == 4_718_592
    assert p["attention"] + p["indexer"] + p["router"] == 21_397_504
    assert costs_sparse.weight_bytes(cell.model) == pytest.approx(3.57e9,
                                                                  rel=0.002)
    assert costs_sparse.kv_bytes_per_token_layer(cell.model) == 2048
    assert costs_sparse.index_key_bytes_per_token(cell.model) == 128
    assert costs_sparse.cache_bytes_per_token(cell.model) == 12 * 2176
    ragged = cell.role["engine"]["ragged"]
    pool = ragged["memory_config_blocks"] * ragged["block_size"] \
        * costs_sparse.cache_bytes_per_token(cell.model)
    assert pool == pytest.approx(6.52e9, rel=0.002)
    # the eight longest requests at once: never preempted
    longest = cell.traffic["prompt_tokens"]["max"] \
        + cell.traffic["answer_tokens"]["max"]
    assert longest <= cell.model["max_position_embeddings"]
    assert 8 * -(-longest // ragged["block_size"]) \
        < ragged["memory_config_blocks"]
    # the program's pools are those bytes
    from deepspeed_tpu.models import mixtral
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    shapes = jax.eval_shape(lambda: mixtral.init_paged_cache(
        cfg, ragged["memory_config_blocks"], ragged["block_size"]))
    assert sum(s.size * s.dtype.itemsize
               for s in jax.tree.leaves(shapes)) == pool
    assert shapes["kI"].shape == (12, 7808, 1, 16, 128)


def test_the_floors_on_hand_computed_numbers():
    model = manifest.Cell(CELL).model
    # 512 rows at a context of 10 000: 2 x 1024 operations a scored pair
    scored = 512 * 10_000
    assert costs_sparse.index_floor_s(model, scored, 10_512, PEAKS) == \
        pytest.approx(2 * scored * 1024 / 197e12)
    # 8 decode rows: bytes bind - each row's 128 B keys once
    assert costs_sparse.index_floor_s(model, 80_000, 80_000, PEAKS) == \
        pytest.approx(max(80_000 * 128 / 819e9,
                          2 * 80_000 * 1024 / 197e12))
    # attention: 4 x 4096 operations a selected pair; a decode row reads its
    # selected keys and values, 2048 B a token
    assert costs_sparse.attn_floor_s(model, 8 * 2048, 8 * 2048, PEAKS) == \
        pytest.approx(8 * 2048 * 2048 / 819e9)
    assert costs_sparse.attn_floor_s(model, 512 * 2048, 2048, PEAKS) == \
        pytest.approx(4 * 512 * 2048 * 4096 / 197e12)


# -- the readers, on hand-made spans and operations -------------------------- #
def synthetic(ctx_tokens=10_000, topk=2048):
    """Two ticks of a mixed program: 8 decode rows at ``ctx_tokens`` each
    and a 512-token chunk at that offset, with what one layer's selection
    did on the span; the indexer's operations take 800 ns and the
    selection's 400 ns of each tick's 4000."""
    model = {**manifest.Cell(CELL).model, "num_hidden_layers": 2}
    model["sa_config"] = {**model["sa_config"], "topk": topk}
    rows = [ctx_tokens + 1] * 8
    chunk = [ctx_tokens + 1 + i for i in range(512)]
    spans, ops = [], []
    for t0 in (0, 5000):
        spans += [span("sched_tick", t0, t0 + 4500),
                  span("decode_step", t0 + 10, t0 + 4400, batch=8,
                       sparse_ctx_scored=sum(rows),
                       sparse_kv_selected=sum(min(c, topk) for c in rows),
                       chunk_tokens=512, chunk_ctx=ctx_tokens,
                       chunk_sparse_ctx_scored=sum(chunk),
                       chunk_sparse_kv_selected=sum(min(c, topk)
                                                    for c in chunk))]
        where = "jit(decode_chunk)/kv_write/while/body/attn/"
        ops += [(Op("fusion.1", t0 + 100, t0 + 300, "xla"),
                 where + "attn_index/dot"),
                (Op("paged_index_scores.21", t0 + 300, t0 + 900, "mosaic"),
                 where + "attn_index/pallas_call"),
                (Op("paged_sparse_select.20", t0 + 900, t0 + 1300, "mosaic"),
                 where + "attn_select/pallas_call"),
                (Op("paged_sparse_prefill.10", t0 + 1300, t0 + 3000,
                    "mosaic"), where + "pallas_call"),
                (Op("paged_sparse_decode.10", t0 + 3000, t0 + 3600,
                    "mosaic"), where + "pallas_call"),
                (Op("fusion.9", t0 + 3600, t0 + 4100, "xla"),
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


def test_the_scope_shares_are_the_scopes_device_time():
    ctx = synthetic()
    assert read(ctx, "serve_sparse_index_share") == pytest.approx(
        100 * 800 / 4000)
    assert read(ctx, "serve_sparse_select_share") == pytest.approx(
        100 * 400 / 4000)
    # the fixed reader books both to ``attn``
    assert manifest.reader("scope_share").read(ctx, scopes=["attn"]) == \
        pytest.approx(100 * 3500 / 4000)


def test_kv_selected_share_is_how_sparse_the_window_was():
    ctx = synthetic()
    scored = 8 * 10_001 + sum(10_001 + i for i in range(512))
    assert read(ctx, "sparse_kv_selected_share") == pytest.approx(
        100 * 520 * 2048 / scored)
    # every context under topk: the selection selects everything
    assert read(synthetic(ctx_tokens=1000), "sparse_kv_selected_share") == 100


@pytest.mark.parametrize("ctx_tokens", [1000, 10_000, 30_000])
def test_the_rooflines_are_the_floors_over_the_kernels_time(ctx_tokens):
    ctx = synthetic(ctx_tokens)
    model = ctx["cell"].model
    rows = 8 * (ctx_tokens + 1)
    chunk = sum(ctx_tokens + 1 + i for i in range(512))
    floor = 2 * 2 * (
        costs_sparse.index_floor_s(model, rows, rows, PEAKS)
        + costs_sparse.index_floor_s(model, chunk, ctx_tokens + 512, PEAKS))
    assert read(ctx, "sparse_index_roofline") == pytest.approx(
        100 * floor / 1200e-9)
    picked = lambda contexts: sum(min(c, 2048) for c in contexts)
    floor = 2 * 2 * (
        costs_sparse.attn_floor_s(model, picked([ctx_tokens + 1] * 8),
                                  picked([ctx_tokens + 1] * 8), PEAKS)
        + costs_sparse.attn_floor_s(
            model, picked(ctx_tokens + 1 + i for i in range(512)),
            min(ctx_tokens + 512, 2048), PEAKS))
    assert read(ctx, "sparse_attn_roofline") == pytest.approx(
        100 * floor / 4600e-9)
    assert read({**ctx, "peaks": None}, "sparse_attn_roofline") is None


def test_no_roofline_can_pass_100_at_the_kernels_own_floor():
    """A kernel that took exactly its floor reads 100; the floors count
    less than any implementation must do (one read of the keys, the
    selected pairs' operations), so a real one reads under."""
    model = manifest.Cell(CELL).model
    for rows, ctx_tokens in ((8, 30_000), (512, 6144)):
        scored = rows * ctx_tokens
        floor = costs_sparse.index_floor_s(model, scored, ctx_tokens, PEAKS)
        # any implementation reads every key once and multiplies every pair
        assert floor <= max(ctx_tokens * 128 / 819e9,
                            2 * scored * 1024 / 197e12) * (1 + 1e-9)


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("path", [OLDER, RECORDED])
def test_a_program_without_a_selection_reports_nothing(monkeypatch, name,
                                                       path):
    """The parent commit's traces: no ``dstpu:`` spans at all, or spans and
    scopes that know no selection."""
    ctx = _ctx(monkeypatch, path, model=manifest.Cell(CELL).model)
    ctx["peaks"] = PEAKS
    assert read(ctx, name) is None


# -- the manifest and the cell ------------------------------------------------ #
def test_the_manifest_adds_one_cell_and_five_metrics():
    b = manifest.manifest()
    by = {m["name"]: m for m in b["per_layer"]}
    assert NEW <= set(by)
    for name in NEW:
        assert by[name]["workloads"] == [CELL]
    assert [m["name"] for m in b["per_layer"][-5:]] == [
        "serve_sparse_index_share", "serve_sparse_select_share",
        "sparse_kv_selected_share", "sparse_index_roofline",
        "sparse_attn_roofline"]
    assert b["workloads"][-1]["name"] == CELL and b["workloads"][-1]["chips"] == 1
    assert b["configs"][-1]["file"].endswith("keye-vl-2.0-30b-a3b.json")
    # dead readers, costs that know neither this cache nor an expert's own
    # width, and a lead that needs a launch to find the device idle (this
    # cell's never is)
    for name in ("prefill_chunk_ms_p50", "sched_host_ms_p50",
                 "decode_hbm_share", "paged_decode_roofline",
                 "decode_live_tile_share", "moe_experts_roofline",
                 "serve_launch_lead_ms_p50"):
        assert CELL not in by[name]["workloads"], name
    for name in ("serve_moe_router_share", "moe_padded_row_share",
                 "decode_step_ms_p50", "serve_attn_share"):
        assert by[name]["workloads"][-1] == CELL, name
    mine = {m["name"] for m in manifest.Cell(CELL).metrics("end_to_end")}
    assert mine == {"serve_tokens_per_s", "itl_p99_ms", "setup_s"}


def test_the_configuration_states_what_a_probe_is_held_to():
    """The two limits of ``reference/keye.py``'s comparison are the
    configuration's, between the readings PERF.md gives (the right form's
    largest 0.0303 and least 0.99387; the nearest wrong form's 0.067, and
    0.9802 with the index keys in fp8), and the rehearsal's widths have
    their own; the family's program is built with the
    cell's block size, chunk and precision."""
    from benchmark.families import keye as family

    cell = manifest.Cell(CELL)
    role = family.serve_role(cell.model)
    assert role == config_file()["roles"]["serve"] and {
        k: role[k] for k in cell.role} == cell.role
    held = role["held"]
    assert 0.0303 < held["logits_mean_abs_diff"] < 0.067
    assert 0.9802 < held["selected_share"] < 0.99387
    toy = manifest.Cell(CELL, rehearsal=True)
    role = family.serve_role(toy.model)
    assert role["engine"] == toy.role["engine"]
    assert role["held"]["logits_mean_abs_diff"] == 0.2
    assert keye.disagreements(
        {"logits_mean_abs_diff": 0.03, "selected": [
            {"layer": 0, "share": 0.995, "counts_equal": True}]}, held) == []
    beyond = keye.disagreements(
        {"logits_mean_abs_diff": 0.067, "selected": [
            {"layer": 0, "share": 0.98, "counts_equal": True},
            {"layer": 11, "share": 1.0, "counts_equal": False}]}, held)
    assert len(beyond) == 3 and "ANOTHER COUNT" in beyond[2]
    assert keye.disagreements({"logits_mean_abs_diff": float("nan"),
                               "selected": []}, held)


def test_the_reference_and_its_variants_are_named():
    assert set(keye_variants.NAMES) == {"no_selection", "newest_topk",
                                        "half_topk", "no_index_rope"}
    with pytest.raises(ValueError):
        keye_variants.logits("unheard_of", {}, None, None)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        keye._published({**manifest.Cell(CELL).model,
                         "tie_word_embeddings": True})


def test_the_cell_rehearses_through_the_real_command_line():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000011", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert "metrics" not in last
    # 4 of 8 experts held, 4 a token: half the computed rows are padding
    assert last["rehearsal"]["moe_padded_row_share"]["value"] == \
        pytest.approx(50.0)
    # prompts of 40-120 tokens at a toy topk of 32: the window's rows read
    # a good half of what they score, never all of it
    assert 30 < last["rehearsal"]["sparse_kv_selected_share"]["value"] < 90
    probes = next(ln for ln in lines if ln.get("phase") == "probes")
    for p in probes["served_token_checks"]:
        assert p["margins"] == [None] * p["served"]
    # every probe was held to the logits and the selected sets too, at the
    # rehearsal's limits, before its tokens were judged
    held = [ln for ln in lines if ln.get("phase") == "held"]
    assert [h["tokens"] for h in held] == [
        p["prompt"] + p["served"] - 1 for p in probes["served_token_checks"]]
    for h in held:
        assert h["why_not"] == [] and h["limits"] == {
            "logits_mean_abs_diff": 0.2, "selected_share": 0.95}
        assert [s["layer"] for s in h["selected"]] == [0, 1]
