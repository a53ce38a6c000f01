"""What ISSUE 26 added for ``olmoe-1b-7b.serve-longprompt``: the plain
reference's own mathematics, the expert bank's bytes and operations on
hand-computed numbers, the two readers on hand-made spans and operations, the
configuration file, and the cell through the real command line. (Program
against reference: ``tests/test_olmoe.py``, tier-1.)"""

import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import costs, costs_moe, manifest
from benchmark.harness import program_spans as ps
from benchmark.harness.trace import Op, Trace
from benchmark.reference import blocks, olmoe, olmoe_variants
from benchmark.traffic_kinds.closed_loop import judge_probes

from test_program_spans import OLDER, RECORDED, _Cell, _ctx, span

CELL = "olmoe-1b-7b.serve-longprompt"
MIXTRAL = "mixtral-8x7b.serve-longprompt"
CALLS = ["prefill_chunk", "prefill_batch", "decode_step"]


# -- the reference ----------------------------------------------------------- #
def test_routes_eight_experts_with_unnormalised_weights_under_one():
    cfg = {"num_experts": 64, "num_experts_per_tok": 8,
           "norm_topk_prob": False}
    logits = jax.random.normal(jax.random.PRNGKey(0), (5, 64))
    dense = np.asarray(olmoe.route(logits, cfg))
    p = np.asarray(jax.nn.softmax(logits, axis=-1))
    assert ((dense > 0).sum(-1) == 8).all()
    chosen = np.sort(p, axis=-1)[:, -8:]
    assert np.allclose(np.sort(dense, axis=-1)[:, -8:], chosen, atol=1e-7)
    assert (dense.sum(-1) < 0.9).all()       # eight of 64: well under one
    renormed = np.asarray(olmoe.route(logits, {**cfg, "norm_topk_prob": True}))
    assert np.allclose(renormed.sum(-1), 1.0, atol=1e-6)


def test_the_qk_norm_runs_over_the_whole_projection():
    a = jax.random.normal(jax.random.PRNGKey(1), (3, 4 * 16)) \
        * jnp.repeat(jnp.asarray([0.5, 1.0, 2.0, 4.0]), 16)   # uneven heads
    weight = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (64,))
    whole = olmoe.projection_norm(a, weight, 4, 1e-5)
    want = a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-5) * weight
    assert np.allclose(np.asarray(whole), np.asarray(want), atol=1e-6)
    # the heads keep their uneven sizes; a per-head norm would level them
    rms = lambda x: np.sqrt(np.mean(np.asarray(x / weight).reshape(
        3, 4, 16) ** 2, -1))
    assert (rms(whole)[:, 3] > 3 * rms(whole)[:, 0]).all()
    per_head = olmoe_variants.per_head_norm(a, weight, 4, 1e-5)
    assert np.allclose(rms(per_head), 1.0, atol=1e-3)


def test_no_margin_means_every_position_is_held_flat():
    cfg = dict(hidden_size=32, intermediate_size=16, num_attention_heads=2,
               num_key_value_heads=2, num_hidden_layers=1, num_experts=4,
               num_experts_per_tok=2, norm_topk_prob=False, rms_norm_eps=1e-5,
               rope_theta=10000, vocab_size=64)
    key = iter(jax.random.split(jax.random.PRNGKey(3), 32))
    mat = lambda *shape: jax.random.normal(next(key), shape) * shape[0] ** -0.5
    layer = {"attn_norm": jnp.ones(32), "ffn_norm": jnp.ones(32),
             "q_norm": jnp.ones(32), "k_norm": jnp.ones(32),
             "q": mat(32, 32), "k": mat(32, 32), "v": mat(32, 32),
             "o": mat(32, 32), "router": mat(32, 4),
             "experts": [(mat(32, 16), mat(32, 16), mat(16, 32))
                         for _ in range(4)]}
    weights = types.SimpleNamespace(embed=mat(64, 32), final_norm=jnp.ones(32),
                                    head=mat(32, 64), layer=lambda i: layer)
    row = np.arange(12) % 64
    got, margin = olmoe.logits_and_margin(cfg, weights, row)
    assert np.array_equal(np.asarray(got),
                          np.asarray(olmoe.logits(cfg, weights, row)))
    assert margin.shape == (12,) and bool(jnp.isinf(margin).all())
    # ... which judge_probes reads as decided, with none allowed beyond
    assert judge_probes([{"gaps": [0.0, 0.39], "margins": [None, None]}]) == []
    assert judge_probes([{"gaps": [0.0, 0.41], "margins": [None, None]}])
    with pytest.raises(ValueError, match="clip_qkv"):
        olmoe.logits({**cfg, "clip_qkv": 8.0}, weights, row)


def test_the_variants_are_other_models():
    assert set(olmoe_variants.NAMES) == {"per_head_norm", "rolled_experts",
                                         "renormalised_gates"}
    with pytest.raises(ValueError):
        olmoe_variants.logits("unheard_of", {}, None, None)
    x = jax.random.normal(jax.random.PRNGKey(4), (7, 32))
    assert blocks.rms_norm(x, jnp.ones(32), 1e-5).shape == x.shape


# -- bytes and operations of the expert bank --------------------------------- #
OLMOE = {"hidden_size": 2048, "intermediate_size": 1024, "num_experts": 64,
         "num_experts_per_tok": 8, "num_hidden_layers": 8}
MIX = {"hidden_size": 4096, "intermediate_size": 14336,
       "num_local_experts": 8, "num_experts_per_tok": 2}
PEAKS = types.SimpleNamespace(hbm_bytes_per_s=819e9, bf16_flops=197e12)


def test_costs_moe_on_hand_computed_numbers():
    assert costs_moe.experts(OLMOE) == 64 and costs_moe.experts(MIX) == 8
    assert costs.experts(OLMOE) == 1       # why this module exists
    with pytest.raises(KeyError):
        costs_moe.experts({"hidden_size": 8})
    # one expert: 3 x 2048 x 1024 weights = 6 291 456; in bf16 12 582 912 B
    assert costs.ffn_params(OLMOE) == 6_291_456
    # a 256-token chunk reaches all 64: 64 x 12.58 MB = 805.3 MB
    assert costs_moe.experts_touched(OLMOE, 256) == pytest.approx(64, abs=1e-9)
    assert costs_moe.bank_bytes(OLMOE, 256) == pytest.approx(805_306_368)
    # 16 decode rows: 64 x (1 - (7/8)^16) = 56.44 experts
    assert costs_moe.experts_touched(OLMOE, 16) == pytest.approx(
        64 * (1 - 0.875 ** 16))
    assert costs_moe.experts_touched(MIX, 16) == pytest.approx(
        8 * (1 - 0.75 ** 16))
    # routed rows only: 256 x 8 rows x 2 x 6 291 456 = 25.77 GFLOP
    assert costs_moe.bank_flops(OLMOE, 2048) == 2 * 2048 * 6_291_456
    # bytes bind: 805.3 MB / 819 GB/s = 0.983 ms against 0.131 ms of matmul
    assert costs_moe.bank_floor_s(OLMOE, 2048, PEAKS) == pytest.approx(
        805_306_368 / 819e9)
    # a call long enough is bound by its operations: 8192 tokens of Mixtral
    rows = 8192 * 2
    assert costs_moe.bank_floor_s(MIX, rows, PEAKS) == pytest.approx(
        2 * rows * 3 * 4096 * 14336 / 197e12)


# -- the readers, on hand-made spans and operations -------------------------- #
def synthetic(model):
    """Two ticks: a 256-token chunk and a 16-row decode each, as OLMoE's
    spans carry them; the bank's operations under ``moe_experts`` take
    3000 ns of the window's 10 000."""
    spans, ops = [], []
    for t0 in (0, 5000):
        spans += [span("sched_tick", t0, t0 + 4000),
                  span("prefill_chunk", t0 + 10, t0 + 1900, tokens=256,
                       moe_rows_routed=2048, moe_rows_computed=16384),
                  span("decode_step", t0 + 2000, t0 + 3900, batch=16,
                       moe_rows_routed=128, moe_rows_computed=1024),
                  span("prefill_batch", t0 + 3950, t0 + 3990, n=1)]  # dense
        ops += [(Op("fusion.1", t0 + 100, t0 + 1100, "xla"),
                 "jit(chunk_prefill)/kv_write/while/body/moe_experts/dot"),
                (Op("fusion.2", t0 + 1100, t0 + 1400, "xla"),
                 "jit(chunk_prefill)/kv_write/while/body/moe_router/dot"),
                (Op("fusion.3", t0 + 2100, t0 + 2600, "xla"),
                 "jit(decode)/kv_write/while/body/moe_experts/dot"),
                (Op("paged_decode.3", t0 + 2600, t0 + 3600, "mosaic"),
                 "jit(decode)/kv_write/while/body/attn/pallas_call")]
    plane = "/device:TPU:0"
    trace = Trace({plane: [op for op, _ in ops]}, {plane: []},
                  [("window", 0, 10000)])
    return {"cell": _Cell("synthetic", model=model), "trace": trace,
            "peaks": PEAKS, "program_spans": ps.Program(ps.link(spans),
                                                        {plane: ops})}


def test_moe_padded_row_share_is_one_less_routed_over_computed():
    ctx = synthetic(OLMOE)
    read = manifest.reader("moe_padded_row_share").read
    assert read(ctx, spans=CALLS) == pytest.approx(
        100 * (1 - (2048 + 128) / (16384 + 1024)))        # 87.5 %
    assert read(ctx, spans=["decode_step"]) == pytest.approx(87.5)
    assert read(ctx, spans=["prefill_batch"]) is None     # no such argument


def test_moe_experts_roofline_is_the_floor_over_the_scopes_device_time():
    ctx = synthetic(OLMOE)
    floor = 8 * 2 * (costs_moe.bank_floor_s(OLMOE, 2048, PEAKS)
                     + costs_moe.bank_floor_s(OLMOE, 128, PEAKS))
    got = manifest.reader("moe_experts_roofline").read(ctx, spans=CALLS)
    assert got == pytest.approx(100 * floor / 3000e-9)
    router = manifest.reader("scope_share").read(ctx, scopes=["moe_router"])
    assert router == pytest.approx(100 * 600 / 5600)
    no_peaks = {**ctx, "peaks": None}
    assert manifest.reader("moe_experts_roofline").read(
        no_peaks, spans=CALLS) is None


@pytest.mark.parametrize("reader", ["moe_padded_row_share",
                                    "moe_experts_roofline"])
@pytest.mark.parametrize("path", [OLDER, RECORDED])
def test_a_program_without_the_span_arguments_reports_nothing(
        monkeypatch, reader, path):
    """The parent commit's traces: no ``dstpu:`` spans at all (the older
    file), or spans that carry no ``moe_rows_*`` (the recorded program)."""
    ctx = _ctx(monkeypatch, path, model=OLMOE)
    ctx["peaks"] = PEAKS
    assert manifest.reader(reader).read(ctx, spans=CALLS) is None
    assert manifest.reader(reader).read(
        {"cell": ctx["cell"], "trace": None}, spans=CALLS) is None


# -- the configuration and the cell ------------------------------------------ #
def test_the_roles_cut_depth_only_and_the_file_says_what_runs():
    data = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "configs", "olmoe-1b-7b.json"))
    assert data["reduced"] == ["num_hidden_layers"]
    for role in data["roles"].values():
        assert set(role["model"]) == {"num_hidden_layers"}
    # WIDTH_KEYS does not know ``num_experts`` (PERF.md section 7): nothing
    # but this test would stop a role from cutting it
    assert "num_experts" not in manifest.WIDTH_KEYS
    cell = manifest.Cell(CELL)
    for key, value in data["published"].items():
        if key != "num_hidden_layers":
            assert cell.model[key] == value, key
    assert cell.model["num_hidden_layers"] == 8
    assert cell.model["max_position_embeddings"] == 4096   # not cut
    # the top level is the configuration as it is run
    assert {k: data[k] for k in data["published"]} == cell.model
    # engine and scheduler are the Mixtral cell's, key for key
    mixtral = manifest.Cell(MIXTRAL)
    assert cell.role["engine"] == mixtral.role["engine"]
    assert cell.role["scheduler"] == mixtral.role["scheduler"]
    assert cell.traffic == mixtral.traffic
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    assert (cfg.num_experts, cfg.top_k, cfg.intermediate_size) == (64, 8, 1024)
    assert cfg.qk_proj_norm and not cfg.norm_topk_prob and not cfg.drop_tokens
    assert cfg.num_kv_heads == cfg.num_heads == 16 and cfg.head_size == 128
    with pytest.raises(ValueError, match="norm_topk_prob"):
        cell.family.build_cfg(cell.model, drop_tokens=False)


def test_the_manifest_reports_the_moe_metrics_in_both_moe_cells():
    b = manifest.manifest()
    by = {m["name"]: m for m in b["per_layer"]}
    for name in ("serve_moe_router_share", "moe_padded_row_share",
                 "moe_experts_roofline"):
        assert by[name]["workloads"] == [MIXTRAL, CELL]
    assert CELL not in by["decode_hbm_share"]["workloads"]
    mine = {m["name"] for m in manifest.Cell(CELL).metrics("per_layer")}
    theirs = {m["name"] for m in manifest.Cell(MIXTRAL).metrics("per_layer")}
    assert theirs - mine == {"decode_hbm_share"} and mine <= theirs


def test_the_cell_rehearses_through_the_real_command_line():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert "metrics" not in last
    # the rehearsal's 8 experts, 4 a token: half the computed rows are padding
    assert last["rehearsal"]["moe_padded_row_share"]["value"] == \
        pytest.approx(50.0)
    probes = next(ln for ln in lines if ln.get("phase") == "probes")
    for p in probes["served_token_checks"]:
        assert p["margins"] == [None] * p["served"]
