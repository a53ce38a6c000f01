"""Telemetry event-schema contract, checkable in CI.

Every event the framework emits is a ``(name, value, step)`` triple whose
name follows the ``Group/.../metric`` convention: a capitalized group
(``Train``, ``Comm``, ``Memory``, ``Reliability``, ``Serving``,
``Telemetry``), at least one more ``/``-separated segment, and a final
metric segment. Consumers (``telemetry_report.py``, the Prometheus mapper,
dashboards) key off this shape, so a malformed name is a silent data loss —
:func:`validate_events` turns it into a tier-1 test failure instead.

Checked invariants:

- name matches ``^[A-Z][A-Za-z0-9_]*(/[A-Za-z0-9_.\\-]+)+$``;
- value is a finite number;
- step is a non-negative integer;
- steps are monotonically NON-DECREASING per series (a series that jumps
  backwards breaks every "last sample wins" consumer);
- ``Serving/*`` names come from the CLOSED registry below — the serving
  engine's counter families are enumerated per metric, so a typo'd or
  unregistered serving series (which ``telemetry_report.py --serving`` and
  the Prometheus mapper would silently ignore) fails validation instead;
- ``Train/overlap/*``, ``Train/remat/*`` and ``Train/attn/*`` names come
  from the closed ``TRAIN_SERIES`` registry (layer-prefetch gauges,
  per-remat-policy sweep rows, and the native-GQA KV-traffic accounting);
  ``Train/Step/*`` names come from the closed ``TRAIN_STEP_SERIES``
  registry (the hub's step-breakdown timer drains — the online tuner
  scores knobs against these); other ``Train/*`` families
  (``Train/Samples``) stay open.
- ``Tune/*`` names follow the Compile shape: the ``Tune/total/*`` rollup
  family is fully enumerated and per-knob ``Tune/knob/<name>/<metric>``
  series carry an open knob-name segment over the closed
  ``TUNE_KNOB_METRICS`` set (the self-tuning runtime — docs/tuning.md).
- ``Comm/*`` names are closed per METRIC: op names are open-ended (any
  collective the comms logger observes), but the final metric segment must
  come from ``COMM_METRICS`` and the ``Comm/total/*`` rollup family from
  ``COMM_TOTAL_SERIES`` — a typo'd byte-accounting suffix (which the
  ``--comm-efficiency`` report would silently drop) fails validation.
- ``Compile/*`` names follow the same shape: program names are open-ended
  (any entry point registered with the CompileMonitor), but the metric
  suffix must come from ``COMPILE_METRICS``, the ``Compile/total/*``
  rollup family from ``COMPILE_TOTAL_SERIES`` and the process-wide compile
  account's ``Compile/process/*`` from ``COMPILE_PROCESS_SERIES``;
- ``Anomaly/*`` names come from the CLOSED ``ANOMALY_SERIES`` registry (the
  step-time/per-phase spike+drift series and the per-host straggler);
- ``Train/mfu/*`` and ``Serving/mfu/*`` carry one lowercase snake_case
  program segment (``MFU_SEGMENT_RE``) — the per-program MFU attribution
  gauges, plus the ``total``/``headline`` rollups.
"""

from __future__ import annotations

import math
import re
from typing import Any, Dict, Iterable, List, Tuple

__all__ = ["EVENT_NAME_RE", "SERVING_SERIES", "TRAIN_SERIES",
           "TRAIN_STEP_SERIES", "SCORE_SERIES",
           "COMM_METRICS", "COMM_TOTAL_SERIES", "COMM_RING_SERIES",
           "COMPILE_METRICS", "COMPILE_TOTAL_SERIES",
           "COMPILE_PROCESS_SERIES", "ANOMALY_SERIES",
           "MEMORY_TIER_SERIES", "RELIABILITY_ELASTIC_SERIES",
           "RELIABILITY_INTEGRITY_SERIES",
           "TENANT_METRICS", "FLEET_REPLICA_METRICS", "FLEET_AGG_SERIES",
           "FLEET_OUTLIER_SERIES", "TRACER_INSTANTS", "TRACER_SPANS",
           "DRAIN_CAUSES",
           "TUNE_TOTAL_SERIES", "TUNE_KNOB_METRICS",
           "MFU_SEGMENT_RE", "ANOMALY_PHASES",
           "REMAT_POLICIES", "validate_events", "validate_jsonl_records"]

EVENT_NAME_RE = re.compile(r"^[A-Z][A-Za-z0-9_]*(/[A-Za-z0-9_.\-]+)+$")

# Registered Serving/* series — every counter/gauge the v2 serving engine
# emits (engine_v2: prefix_cache_events, latency_events, spec_events).
# Adding an engine counter REQUIRES registering its name here, or the tier-1
# event-schema tests fail on the first run that emits it.
SERVING_SERIES = frozenset(
    ["Serving/prefix_cache/" + m for m in (
        "lookups", "hits", "hit_tokens", "prefill_tokens_saved",
        "evictions", "cow_copies", "retained_blocks",
        # host-spill tier (inference.prefix_cache.host_spill; docs/memory.md)
        "spills", "restores", "restored_tokens", "spilled_blocks")]
    + [f"Serving/latency/{m}_{s}"
       for m in ("ttft_ms", "itl_ms", "queue_ms", "e2e_ms")
       for s in ("p50", "p90", "p99", "count")]
    # quantized KV cache (inference.kv_quant; docs/serving.md "Quantized
    # KV cache" — engine_v2.kv_quant_events)
    + ["Serving/kv_quant/" + m for m in (
        "blocks_quantized", "bytes_saved", "max_abs_err", "dequant_fused")]
    # recurrent state (a family with state-space layers; docs/serving.md
    # "Recurrent state" - engine_v2.state_events)
    + ["Serving/state/" + m for m in ("bytes", "bytes_per_slot", "slots_held")]
    # a learned token selection inside attention (docs/serving.md "Learned
    # token selection" - engine_v2.sparse_events): ONE layer's counts
    + ["Serving/sparse/" + m for m in ("rows", "ctx_scored", "kv_selected")]
    # kinds of KV state (a family with sliding-window layers;
    # docs/serving.md "Kinds of KV state" - engine_v2.kv_kind_events)
    + ["Serving/kv/" + m for m in (
        "full_blocks_live", "window_blocks_live", "window_blocks_released",
        # a latent (MLA) cache's one pool (docs/serving.md "Latent (MLA)
        # cache")
        "latent_blocks_live")]
    # what step() ran (engine_v2.engine_events): its calls, those whose
    # prefill chunk rode in the decode program (``decode_chunk``), those
    # launched while the program before was still unread, the token rows
    # its programs ran and the rows their heads scored
    + ["Serving/engine/" + m for m in (
        "steps", "mixed_steps", "overlapped_steps", "rows", "head_rows")]
    + ["Serving/spec/" + m for m in (
        "verify_steps", "decode_steps", "step_seqs", "drafted_tokens",
        "accepted_tokens", "emitted_tokens", "rolled_back_tokens",
        "verify_positions", "verify_capacity", "accept_rate",
        "mean_accepted_len", "tokens_per_step", "verify_batch_occupancy")]
    # continuous-batching scheduler (serving/scheduler.py sched_events)
    + ["Serving/sched/" + m for m in (
        "submitted", "admitted", "resumed", "preempted", "rejected",
        "expired", "completed", "slo_met", "slo_missed", "ticks",
        "chunked_admissions", "tokens_emitted",
        # what the ticks did, counted where the work happens: prompt tokens
        # whose KV a tick wrote, sequences in its decode batch, and ticks
        # that carried prefill work (the sched_tick span's arguments, summed)
        "prefill_tokens", "decode_seq_steps", "chunk_ticks", "queue_depth",
        "queue_wait_ms_p50", "queue_wait_ms_p90", "queue_wait_ms_p99",
        "queue_wait_ms_count", "goodput_frac", "goodput_rps")]
    # multi-replica router (serving/router.py router_events)
    + ["Serving/router/" + m for m in (
        "requests", "affinity_hits", "session_hits", "load_fallbacks",
        "reject_fallbacks", "drains", "replicas")]
    # fleet resilience (serving/router.py fleet_events — circuit breakers,
    # crash failover, overload degradation; docs/serving.md "Fleet fault
    # tolerance")
    + ["Serving/fleet/" + m for m in (
        "failovers", "replayed_tokens", "tick_faults", "slow_ticks",
        "probe_ticks", "circuit_open", "circuit_half_open", "circuit_closed",
        "shed_requests", "degrade_level", "degrade_shifts",
        "broken_replicas")]
    # disaggregated prefill/decode (serving/router.py disagg_events —
    # chain-hash-keyed paged-KV handoff over the int8 wire format;
    # docs/serving.md "Disaggregated prefill/decode")
    + ["Serving/disagg/" + m for m in (
        "handoffs", "blocks_shipped", "wire_bytes", "bf16_equiv_bytes",
        "wire_ratio", "dedup_blocks", "dedup_bytes_saved",
        "import_dropped", "import_failures", "handoff_fallbacks",
        "tier_fallbacks", "prefill_replicas", "decode_replicas")])

# The named remat policies the activation-checkpointing registry ships
# (runtime/activation_checkpointing/checkpointing.py POLICIES — a tier-1
# test pins the two lists equal, so a policy added there must be
# registered here to get its sweep series).
REMAT_POLICIES = ("none", "full", "dots_saveable",
                  "dots_with_no_batch_dims", "save_names", "save_attn_out",
                  "save_big_matmuls", "offload", "offload_dots")

# Registered Train/overlap/* + Train/remat/* series — the training-side
# fine-grained-overlap gauges (engine layer-prefetch config + hub comm
# accounting) and the per-policy remat sweep rows (bench.py remat sweep,
# MemoryTelemetry). Same closed-registry contract as SERVING_SERIES.
TRAIN_SERIES = frozenset(
    ["Train/overlap/" + m for m in (
        "prefetch_depth", "prefetch_layers", "prefetch_bytes",
        "hidden_comm_frac")]
    + [f"Train/remat/{m}_{p}" for p in REMAT_POLICIES
       for m in ("saved_bytes", "peak_bytes", "step_ms")]
    # the rung the engine chose for ``remat: true`` with no policy named
    # (engine._report_remat, after a batch signature's first compile): its
    # index on checkpointing.LADDER (0 = richest), the bytes it was
    # predicted to keep on a device, the head-room it was given, the
    # predicted and the compiled peak of the step, and compiles that ended
    # in RESOURCE_EXHAUSTED and fell back to ``full``
    + ["Train/remat/" + m for m in (
        "rung", "kept_bytes", "headroom_bytes", "predicted_peak_bytes",
        "compiled_peak_bytes", "fallbacks")]
    # native-GQA attention accounting (attention.gqa_native; bench.py
    # detail.attn_probe GQA sweep — docs/performance.md "Native GQA
    # attention"): per-step K/V HBM bytes the narrow kernels avoid, and
    # the query/kv head ratio they avoid it by
    + ["Train/attn/" + m for m in ("kv_bytes_saved", "gqa_ratio")])

# Registered Train/Step/* series — the hub's step-breakdown drains
# (``hub._STEP_TIMERS`` suffixes) plus the ThroughputTimer tflops gauge.
# CLOSED since the self-tuning runtime (docs/tuning.md): the online tuner
# scores knobs against these names, so an unregistered step series would be
# an unscoreable objective. The suffix list mirrors ANOMALY_PHASES below —
# both key off the same timer drains.
TRAIN_STEP_SERIES = frozenset(
    [f"Train/Step/{p}_ms" for p in ("fwd", "bwd", "step", "train_batch",
                                    "fwd_micro", "bwd_micro", "step_micro",
                                    "eval")]
    + ["Train/Step/tflops"])


# Registered Comm/* byte-accounting metrics (comm.CommsTelemetry.events):
# per-op series are Comm/<op>/<metric> with an OPEN op namespace but a
# CLOSED metric set — the link-class split (algo_bytes_dcn / algo_bytes_ici)
# and the quantized-collective fp32-equivalent accounting added for the
# ZeRO++ trio live here. The Comm/total/* rollup family (TelemetryHub
# _comm_efficiency_events) is fully enumerated.
COMM_METRICS = frozenset((
    "bytes", "count", "algo_bytes", "algo_bytes_dcn", "algo_bytes_ici",
    "fp32_equiv_bytes"))
COMM_TOTAL_SERIES = frozenset(
    "Comm/total/" + m for m in (
        "algo_bytes", "algo_bytes_dcn", "algo_bytes_ici", "busbw_gbps",
        "est_comm_frac"))
# Ring-attention schedule telemetry (sequence/ring.py record_ring →
# CommsTelemetry.ring_stats): hop/byte counts for the KV rotation, the
# measured compute/transfer overlap fraction, and gauges for the active
# schedule knobs + the silent-dense-fallback marker. Fully enumerated —
# Comm/ring/* is NOT part of the per-op Comm/<op>/<metric> namespace.
COMM_RING_SERIES = frozenset(
    "Comm/ring/" + m for m in (
        "hops", "bytes", "overlap_frac", "dense_fallback", "overlap_on",
        "zigzag"))


# Registered Compile/* metrics (telemetry/compile.py CompileMonitor.events):
# per-program series are Compile/<program>/<metric> with an OPEN program
# namespace (any jitted entry point registered with the monitor) but a
# CLOSED metric set; the Compile/total/* rollup family is fully enumerated.
COMPILE_METRICS = frozenset((
    "compiles", "cache_hits", "recompiles", "lower_ms", "compile_ms",
    "cost_flops", "cost_bytes", "peak_memory_bytes", "pool_copy_bytes",
    "aliased_bytes", "analysis_ms", "persistent_cache_hits",
    "persistent_cache_misses"))
COMPILE_TOTAL_SERIES = frozenset(
    "Compile/total/" + m for m in (
        "programs", "compiles", "cache_hits", "recompiles", "lower_ms",
        "compile_ms"))
# Compile/process/*: the process-wide compile account (telemetry/compile.py
# CompileAccount.totals, fed by jax.monitoring) - every program of the
# process, registered with a monitor or not. CLOSED. Here ``cache_hits`` is
# JAX's persistent cache's, not a monitor's dispatch table's.
COMPILE_PROCESS_SERIES = frozenset(
    "Compile/process/" + m for m in (
        "trace_lower_s", "backend_compile_s", "cache_retrieval_s",
        "monitor_analysis_s", "cache_hits", "cache_misses", "cache_requests",
        "programs_compiled"))

# The phase keys the hub's step-breakdown timers can emit (hub._STEP_TIMERS
# event suffixes) — the anomaly detector tracks one series per phase.
ANOMALY_PHASES = ("fwd", "bwd", "step", "train_batch", "fwd_micro",
                  "bwd_micro", "step_micro", "eval")

# Registered Anomaly/* series (telemetry/anomaly.py via the hub): CLOSED —
# an emitted-but-unregistered anomaly name fails tier-1 validation.
ANOMALY_SERIES = frozenset(
    [f"Anomaly/step_time/{k}" for k in ("spike", "drift")]
    + [f"Anomaly/phase/{p}/{k}" for p in ANOMALY_PHASES
       for k in ("spike", "drift")]
    + ["Anomaly/host/straggler"])

# Registered Memory/tier/* series (the tiered memory subsystem —
# memory/tiered_store.py TieredStore.events + the serving engine's KV
# host-spill gauges; docs/memory.md): CLOSED — an emitted-but-unregistered
# tier series fails tier-1 validation. Other Memory/* families
# (Memory/bytes_in_use, Memory/peak_bytes) stay open.
MEMORY_TIER_SERIES = frozenset(
    "Memory/tier/" + m for m in (
        # TieredStore byte accounting + transfer/overlap measurement
        "resident_bytes_host", "resident_bytes_file",
        "transfer_d2h_bytes", "transfer_h2d_bytes",
        "transfer_busy_ms", "overlap_ms", "overlap_frac",
        "prefetch_hits", "prefetch_misses", "offloads", "restores",
        # serving KV host-spill pool (engine_v2.publish_prefix_telemetry)
        "kv_spilled_blocks", "kv_spilled_bytes", "kv_spills",
        "kv_restores"))

# Registered Reliability/elastic/* series (the elastic training runtime —
# universal checkpoint saves/resumes/reshards, heartbeat host-loss
# detection, and the drill verdict; docs/reliability.md "Elastic training &
# universal checkpoint"): CLOSED — an emitted-but-unregistered elastic
# series fails tier-1 validation. Other Reliability/* families (the PR-3
# checkpoint/watchdog counters, violation/<kind>) stay open.
RELIABILITY_ELASTIC_SERIES = frozenset(
    "Reliability/elastic/" + m for m in (
        "saves", "resumes", "reshards", "host_loss_detected", "drill_pass"))

# Registered Reliability/integrity/* series (the numerics-integrity plane —
# cross-replica fingerprint votes, shadow recompute audits, suspect-host
# quarantine, and checkpoint walk-back; docs/reliability.md "Numerics
# integrity & SDC"): CLOSED, same contract as the elastic family above.
RELIABILITY_INTEGRITY_SERIES = frozenset(
    "Reliability/integrity/" + m for m in (
        "checks", "mismatches", "attributed_host", "quarantines",
        "walkbacks", "audit_steps"))

# Per-tenant SLO accounting (telemetry/fleet.py TenantSLOAccountant;
# docs/observability.md "Fleet observability"): series are
# Serving/tenant/<slug>/<metric> with an OPEN tenant-slug namespace (the
# accountant sanitizes raw tenant tags onto the event-name grammar) but a
# CLOSED metric set — the same shape as Compile/<program>/<metric>.
TENANT_METRICS = frozenset((
    "completed", "slo_met", "slo_missed", "rejected", "goodput_frac",
    "ttft_p99_ms", "itl_p99_ms", "slo_burn_rate", "slo_burn_alerts"))

# Fleet/* cross-replica rollups (telemetry/fleet.py FleetMetricsAggregator):
# Fleet/replica<N>/<metric> per-replica rows over a CLOSED metric set,
# Fleet/agg/<metric>_{sum,max,min,mean} rollups plus the pooled-sample
# percentile merges (<latency metric>_merged), Fleet/outlier/<latency
# metric> replica-outlier deltas, and the Fleet/replicas gauge.
FLEET_REPLICA_METRICS = frozenset((
    "live", "queue_depth", "completed", "slo_met", "goodput_frac",
    "tokens_emitted", "queue_wait_ms_p99", "ttft_ms_p99", "itl_ms_p99",
    "e2e_ms_p99"))
_FLEET_LATENCY_METRICS = ("queue_wait_ms_p99", "ttft_ms_p99", "itl_ms_p99",
                          "e2e_ms_p99")
FLEET_AGG_SERIES = frozenset(
    [f"Fleet/agg/{m}_{s}" for m in FLEET_REPLICA_METRICS
     for s in ("sum", "max", "min", "mean")]
    + [f"Fleet/agg/{m}_merged" for m in _FLEET_LATENCY_METRICS])
FLEET_OUTLIER_SERIES = frozenset(
    f"Fleet/outlier/{m}" for m in _FLEET_LATENCY_METRICS)
_FLEET_REPLICA_RE = re.compile(r"^Fleet/replica\d+/([A-Za-z0-9_]+)$")

# Registered tracer INSTANT names (trace.Tracer.instant call sites across
# the framework — the flight-recorder grammar consumers like
# telemetry_report --trace key off). CLOSED: a new instant name must be
# registered here (a tier-1 test pins exported traces against this set).
TRACER_INSTANTS = frozenset((
    # tracer/hub internals
    "trace_begin", "anomaly",
    # trace-time marker of the gradient-bucket flush (runtime/engine.py)
    "overlap/bucket_flush",
    # serving request lifecycle (engine_v2)
    "first_token", "parked", "resumed",
    # scheduler + fleet resilience (serving/scheduler.py, fleet.py, router)
    "sched_preempt", "degrade", "rehome", "failover",
    "circuit_open", "circuit_closed",
    # disaggregated prefill→decode KV handoff (serving/router.py)
    "kv_handoff",
    # fleet observability plane (telemetry/fleet.py)
    "trace_handoff", "slo_burn_alert",
    # online tuner arm transitions (tuning/tuner.py — docs/tuning.md)
    "tune_step", "tune_revert"))

# Registered tracer SPAN names (Tracer.span / step_span / begin / complete
# call sites). CLOSED, like the instants. A context-managed span is also on
# the profiler's timeline as ``dstpu:<name>`` (trace.TIMELINE_PREFIX), where
# the benchmark's per-layer metrics find it by this name — renaming one
# changes a yardstick (docs/observability.md "Spans on the profiler
# timeline" has the tree and the arguments).
TRACER_SPANS = frozenset((
    # training (runtime/engine.py): the step and its host phases ...
    "train_step", "train_shard_batch", "train_sync", "train_step_end",
    # ... the dispatch of the step's program(s), and the per-program phases
    # of the breakdown / API-parity paths
    "train/train_batch", "train/fwd", "train/bwd", "train/step",
    "train/fwd_micro", "train/eval_batch",
    # ... and, ahead of a batch signature's first lowering, the choice of
    # the remat rung (engine._remat_for: the chooser's traces)
    "train_remat_choose",
    "checkpoint/save", "checkpoint/publish",
    # one lower + compile of a monitored program (telemetry/compile.py)
    "compile",
    # request lifecycle, ring only (engine_v2; telemetry/fleet.py)
    "request", "replica_leg", "queue_wait", "prefill",
    # scheduler tick and its phases (serving/scheduler.py)
    "sched_tick", "sched_expire", "sched_admit", "sched_preempt_guard",
    "sched_step_engine", "sched_harvest", "sched_retire",
    # one engine dispatch and its host phases (engine_v2). A chunk's
    # multi-token walk rides ``decode_step`` and ``prefill_chunk`` as
    # ``chunk_attn_tiles_{live, grid, table}``, beside the decode walk's
    # ``attn_tiles_{live, grid}`` (and, under a learned selection, the
    # decode rows' index scores' ``index_tiles_{live, grid}`` and
    # ``index_live_tile_share``), and the KV tokens a grid step of that
    # walk took as ``chunk_attn_kv_tile`` (the wide tile where the walk
    # fetches its own pages - there ``_grid == _live`` - else 256, or the
    # wide tile of a long walk; plain numbers; docs/observability.md). A
    # family with recurrent
    # state AND experts (models/nemotron_h.py, the first with both) carries
    # ``ssm_rows`` / ``ssm_tokens`` and ``moe_rows_routed`` /
    # ``moe_rows_computed`` / ``moe_row_tile`` on the SAME ``decode_step``,
    # ``prefill_chunk`` and ``prefill_batch`` spans: the benchmark's
    # ``ssm_grouped_decode_roofline`` reads ``decode_step.ssm_rows``, its
    # ``moe_relu2_experts_roofline`` and ``moe_padded_row_share`` the
    # ``moe_rows_*`` of all three (docs/observability.md "state and experts
    # in one family"). A family that names its recurrent layer's rows
    # itself (``ModelFamily.state_rows``; models/brumby.py, the first with a
    # state and NO cache) carries ``retention_rows`` - the live
    # single-token rows whose state ONE retention layer advances - and
    # ``retention_chunk_rows`` - the rows of the chunk riding with them - on
    # ``decode_step``, and ``retention_chunk_rows`` on ``prefill_chunk``:
    # the benchmark's ``retention_decode_roofline`` and
    # ``retention_chunk_roofline`` read them (docs/observability.md "a
    # state and no cache"). models/solar_open2.py (delta-rule state BESIDE
    # KV blocks, under experts) names its own the same way: ``delta_rows``
    # and ``delta_chunk_rows`` on ``decode_step``, ``delta_chunk_rows`` on
    # ``prefill_chunk``, beside ``ssm_rows`` / ``ssm_tokens`` and the
    # ``moe_rows_*``: ``delta_decode_roofline`` and ``delta_chunk_roofline``
    # read them (docs/observability.md "a delta rule beside blocks"). The
    # state-space families (models/granite_hybrid.py ``state_rows``) say
    # ``ssm_chunk_rows`` on ``decode_step`` and ``prefill_chunk``: the
    # chunk's token rows ONE Mamba layer took through the Mosaic scan
    # ``ssm_chunk_scan``, 0 where the op ran its XLA form; no metric reads it.
    # A family with window KINDS of KV state (models/cohere2_moe.py,
    # models/mellum.py) says ``kv_tokens_full`` / ``kv_tokens_window`` on
    # ``decode_step`` and ``prefill_chunk`` and, on ``decode_step`` alone,
    # ``rows_past_window``: the decode rows whose context is longer than the
    # window (the benchmark's ``decode_rows_past_window`` reads it).
    # models/zaya.py (a tail on the slot pool BESIDE paged keys and values in
    # every layer, under a top-1 bank with a skip output) says ``cca_rows`` -
    # the rows ONE layer's CCA mixing took: the live single-token rows and
    # the chunk's tokens - and ``cca_tail_rows`` - the pool rows their tails
    # came from and went back to - on ``decode_step`` and ``prefill_chunk``
    # (``cca_mix_roofline`` reads both), and ``moe_rows_skipped`` beside
    # ``moe_rows_routed`` / ``moe_rows_computed`` on those and on
    # ``prefill_batch``: the rows ONE router sends to its skip output, in
    # expectation under a uniform router (a shape fact, no count: no
    # counter and no benchmark metric reads it)
    "prefill_batch", "prefill_chunk", "decode_step", "decode_quantum",
    "spec_verify", "engine_prep", "engine_dispatch", "engine_wait",
    "engine_emit",
    # a read of every program in flight, ahead of the tick's own: what
    # needs a token's value or moves a sequence (``DRAIN_CAUSES``)
    "engine_drain",
    # v1 generate loop (inference/engine.py)
    "generate/prefill", "generate/decode_chunk"))

# Why a program in flight is read ahead of the tick's own ``collect``: the
# ``cause`` of an ``engine_drain`` span and the keys of
# ``InferenceEngineV2.drains``, one name a call site (docs/observability.md
# "Every drain has a cause" says what each needs a token's value or a
# slot's position for). CLOSED: the engine counts by these keys and raises
# on any other.
DRAIN_CAUSES = (
    # engine_v2: a one-shot prefill, a speculative step, a fused quantum,
    # finish() of a stream with a token in flight, park, fork,
    # kv_chain_hashes, export_kv_blocks
    "put", "spec", "quantum", "finish", "park", "fork", "prefix_hash",
    "export",
    # serving/scheduler.py: _park_to_queue, evict_all, export_live (the
    # tokens go to their handles before the sequence moves)
    "sched_park", "sched_evict", "sched_export")

# Registered Tune/* series (the self-tuning runtime — tuning/tuner.py;
# docs/tuning.md): the Tune/total/* rollup family is fully enumerated, and
# per-knob series are Tune/knob/<name>/<metric> with an OPEN knob-name
# namespace (any registered tunable — names like ``train.prefetch_depth``
# ride the dot-allowing segment grammar) but a CLOSED metric set, the
# Compile/<program>/<metric> shape.
TUNE_TOTAL_SERIES = frozenset(
    "Tune/total/" + m for m in (
        "trials", "accepts", "reverts", "vetoes", "retunes",
        "open_knobs", "closed_knobs"))
TUNE_KNOB_METRICS = frozenset((
    "trials", "accepts", "reverts", "vetoes", "retunes",
    "score_baseline", "score_best", "score_delta", "value", "active"))

# The union of closed series registries an online tunable may score
# against (tuning/registry.py ``Tunable.score_series``; the knob-coverage
# lint in tests/test_tuning.py checks membership here).
SCORE_SERIES = (TRAIN_STEP_SERIES | TRAIN_SERIES | SERVING_SERIES
                | COMM_RING_SERIES | COMM_TOTAL_SERIES)

# Per-program MFU attribution gauges (Train/mfu/<program>,
# Serving/mfu/<program>, plus the total/headline rollups): the program
# segment is open-ended but must be one lowercase snake_case token — the
# CompileMonitor sanitizes registered names onto this grammar.
MFU_SEGMENT_RE = re.compile(r"^[a-z][a-z0-9_]*$")


def validate_events(events: Iterable[Tuple[str, float, int]]) -> List[str]:
    """Check ``(name, value, step)`` triples against the schema; returns a
    list of human-readable problems (empty = clean)."""
    problems: List[str] = []
    last_step: Dict[str, int] = {}
    for i, ev in enumerate(events):
        try:
            name, value, step = ev[0], ev[1], ev[2]
        except (TypeError, IndexError):
            problems.append(f"event #{i}: not a (name, value, step) triple: "
                            f"{ev!r}")
            continue
        if not isinstance(name, str) or not EVENT_NAME_RE.match(name):
            problems.append(f"event #{i}: name {name!r} violates the "
                            f"Group/.../metric convention")
            continue
        if name.startswith(("Train/mfu/", "Serving/mfu/")):
            seg = name.split("/", 2)[2]
            if "/" in seg or not MFU_SEGMENT_RE.match(seg):
                problems.append(
                    f"event #{i}: mfu series {name!r} does not carry one "
                    f"snake_case program segment "
                    f"(telemetry.schema.MFU_SEGMENT_RE)")
                continue
        elif name.startswith("Serving/tenant/"):
            parts = name.split("/")
            if len(parts) != 4 or parts[3] not in TENANT_METRICS:
                problems.append(
                    f"event #{i}: tenant series {name!r} is not a "
                    f"Serving/tenant/<slug>/<metric> name with a metric "
                    f"from telemetry.schema.TENANT_METRICS")
                continue
        elif name.startswith("Serving/") and name not in SERVING_SERIES:
            problems.append(f"event #{i}: serving series {name!r} is not "
                            f"registered in telemetry.schema.SERVING_SERIES")
            continue
        if name.startswith("Fleet/"):
            m = _FLEET_REPLICA_RE.match(name)
            if m is not None:
                if m.group(1) not in FLEET_REPLICA_METRICS:
                    problems.append(
                        f"event #{i}: fleet replica series {name!r} metric "
                        f"is not registered in "
                        f"telemetry.schema.FLEET_REPLICA_METRICS")
                    continue
            elif name != "Fleet/replicas" and \
                    name not in FLEET_AGG_SERIES and \
                    name not in FLEET_OUTLIER_SERIES:
                problems.append(
                    f"event #{i}: fleet series {name!r} is not registered "
                    f"in telemetry.schema FLEET_AGG_SERIES / "
                    f"FLEET_OUTLIER_SERIES")
                continue
        if name.startswith(("Train/overlap/", "Train/remat/",
                            "Train/attn/")) and \
                name not in TRAIN_SERIES:
            problems.append(f"event #{i}: train series {name!r} is not "
                            f"registered in telemetry.schema.TRAIN_SERIES")
            continue
        if name.startswith("Train/Step/") and \
                name not in TRAIN_STEP_SERIES:
            problems.append(f"event #{i}: step series {name!r} is not "
                            f"registered in "
                            f"telemetry.schema.TRAIN_STEP_SERIES")
            continue
        if name.startswith("Tune/total/"):
            if name not in TUNE_TOTAL_SERIES:
                problems.append(
                    f"event #{i}: tune rollup series {name!r} is not "
                    f"registered in telemetry.schema.TUNE_TOTAL_SERIES")
                continue
        elif name.startswith("Tune/"):
            parts = name.split("/")
            if len(parts) != 4 or parts[1] != "knob" or \
                    parts[3] not in TUNE_KNOB_METRICS:
                problems.append(
                    f"event #{i}: tune series {name!r} is not a "
                    f"Tune/knob/<name>/<metric> name with a metric from "
                    f"telemetry.schema.TUNE_KNOB_METRICS")
                continue
        if name.startswith("Memory/tier/") and \
                name not in MEMORY_TIER_SERIES:
            problems.append(f"event #{i}: memory-tier series {name!r} is not "
                            f"registered in "
                            f"telemetry.schema.MEMORY_TIER_SERIES")
            continue
        if name.startswith("Reliability/elastic/") and \
                name not in RELIABILITY_ELASTIC_SERIES:
            problems.append(
                f"event #{i}: elastic reliability series {name!r} is not "
                f"registered in "
                f"telemetry.schema.RELIABILITY_ELASTIC_SERIES")
            continue
        if name.startswith("Reliability/integrity/") and \
                name not in RELIABILITY_INTEGRITY_SERIES:
            problems.append(
                f"event #{i}: integrity reliability series {name!r} is not "
                f"registered in "
                f"telemetry.schema.RELIABILITY_INTEGRITY_SERIES")
            continue
        if name.startswith("Anomaly/") and name not in ANOMALY_SERIES:
            problems.append(f"event #{i}: anomaly series {name!r} is not "
                            f"registered in telemetry.schema.ANOMALY_SERIES")
            continue
        if name.startswith("Compile/total/"):
            if name not in COMPILE_TOTAL_SERIES:
                problems.append(
                    f"event #{i}: compile rollup series {name!r} is not "
                    f"registered in telemetry.schema.COMPILE_TOTAL_SERIES")
                continue
        elif name.startswith("Compile/process/"):
            if name not in COMPILE_PROCESS_SERIES:
                problems.append(
                    f"event #{i}: compile account series {name!r} is not "
                    f"registered in "
                    f"telemetry.schema.COMPILE_PROCESS_SERIES")
                continue
        elif name.startswith("Compile/"):
            parts = name.split("/")
            if len(parts) != 3 or parts[2] not in COMPILE_METRICS:
                problems.append(
                    f"event #{i}: compile series {name!r} is not a "
                    f"Compile/<program>/<metric> name with a metric from "
                    f"telemetry.schema.COMPILE_METRICS")
                continue
        if name.startswith("Comm/total/"):
            if name not in COMM_TOTAL_SERIES:
                problems.append(
                    f"event #{i}: comm rollup series {name!r} is not "
                    f"registered in telemetry.schema.COMM_TOTAL_SERIES")
                continue
        elif name.startswith("Comm/ring/"):
            if name not in COMM_RING_SERIES:
                problems.append(
                    f"event #{i}: ring comm series {name!r} is not "
                    f"registered in telemetry.schema.COMM_RING_SERIES")
                continue
        elif name.startswith("Comm/") and \
                name.rsplit("/", 1)[-1] not in COMM_METRICS:
            problems.append(
                f"event #{i}: comm metric suffix of {name!r} is not "
                f"registered in telemetry.schema.COMM_METRICS")
            continue
        try:
            v = float(value)
        except (TypeError, ValueError):
            problems.append(f"event #{i} ({name}): non-numeric value "
                            f"{value!r}")
            continue
        if not math.isfinite(v):
            problems.append(f"event #{i} ({name}): non-finite value {v!r}")
        if not isinstance(step, int) or isinstance(step, bool) or step < 0:
            problems.append(f"event #{i} ({name}): step {step!r} is not a "
                            f"non-negative int")
            continue
        prev = last_step.get(name)
        if prev is not None and step < prev:
            problems.append(f"event #{i} ({name}): step {step} < previous "
                            f"step {prev} (series must be monotonic)")
        last_step[name] = step
    return problems


def validate_jsonl_records(records: Iterable[Dict[str, Any]]) -> List[str]:
    """Schema-check JSONL monitor records (``{"name","value","step","ts"}``,
    as loaded by ``telemetry_report.load_events``)."""
    triples = []
    problems: List[str] = []
    for i, r in enumerate(records):
        if not isinstance(r, dict) or "name" not in r or "value" not in r:
            problems.append(f"record #{i}: not an event object: {r!r}")
            continue
        triples.append((r.get("name"), r.get("value"), r.get("step", 0)))
    return problems + validate_events(triples)
