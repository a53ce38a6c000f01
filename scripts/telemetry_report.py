#!/usr/bin/env python
"""Summarize a TelemetryHub JSONL file (the ``jsonl_monitor`` sink).

Reads ``events.jsonl`` lines of ``{"name", "value", "step", "ts"}`` and prints
a step-time / comm-volume / memory summary table — the offline companion to
the live ``log_summary()`` output. Deliberately free of jax/numpy imports so
it runs anywhere a telemetry file lands.

Usage: python scripts/telemetry_report.py runs/job/events.jsonl [--last N]
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
from collections import OrderedDict
from typing import Dict, List


def load_events(*paths: str) -> List[dict]:
    """Load one or more JSONL telemetry files, tolerating the torn tail a
    crash or SIGKILL leaves behind: an unparseable FINAL line is silently
    dropped (that is what a mid-``write(2)`` kill looks like), unparseable
    lines elsewhere are dropped with a stderr warning, and undecodable bytes
    never abort the load. The surviving events still make a full report.

    With MULTIPLE paths (a fleet of per-replica monitor files) the streams
    are concatenated in argument order and every record is provenance-tagged
    with ``"source"`` (the path, disambiguated to its shortest unique
    suffix) so the ``--fleet`` report can say which replica said what. A
    single path keeps the historical untagged record shape."""
    tag = len(paths) > 1
    labels = _source_labels(paths) if tag else {}
    events = []
    for path in paths:
        bad: List[int] = []
        n_lines = 0
        with open(path, encoding="utf-8", errors="replace") as f:
            for n_lines, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    bad.append(n_lines)
                    continue
                if isinstance(rec, dict) and "name" in rec and "value" in rec:
                    if tag:
                        rec["source"] = labels[path]
                    events.append(rec)
        interior = [n for n in bad if n != n_lines]
        if interior:
            print(f"warning: skipped {len(interior)} unparseable interior "
                  f"line(s) in {path} (first at line {interior[0]})",
                  file=sys.stderr)
    return events


def _source_labels(paths) -> Dict[str, str]:
    """Shortest-unique-suffix label per path: a fleet's files are usually
    ``.../replica0/events.jsonl`` vs ``.../replica1/events.jsonl``, where
    the basename alone would collide."""
    out: Dict[str, str] = {}
    for path in paths:
        parts = path.replace(os.sep, "/").split("/")
        for k in range(1, len(parts) + 1):
            label = "/".join(parts[-k:])
            others = [p for p in paths if p != path]
            if all(not p.replace(os.sep, "/").endswith(label)
                   for p in others):
                break
        out[path] = label
    return out


def _series(events: List[dict]) -> "OrderedDict[str, List[dict]]":
    by_name: "OrderedDict[str, List[dict]]" = OrderedDict()
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    return by_name


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:,.1f} {unit}"
        n /= 1024
    return f"{n:,.1f} TiB"


def comm_efficiency(events: List[dict]) -> str:
    """``--comm-efficiency``: collective count, total algorithmic bytes, and
    bytes-per-step from the ``Comm/*`` series — the offline comm-volume
    regression check (comm records are per compiled step, so the last sample
    of each series IS the per-step number; totals scale by executed steps)."""
    steps = sorted({e.get("step", 0) for e in events})
    n_steps = len(steps)
    per_op: Dict[str, Dict[str, float]] = {}
    for e in events:
        name = e["name"]
        if not name.startswith("Comm/") or name.startswith("Comm/total/") \
                or name.startswith("Comm/ring/"):
            continue  # ring schedule gauges get their own section below
        _, op, kind = name.split("/", 2)
        per_op.setdefault(op, {})[kind] = e["value"]  # last sample wins
    if not per_op:
        # no collectives recorded — the ring/overlap/remat/attn gauge
        # sections can still render (bench probes emit them without a
        # comms logger; ring fallback markers record even when disabled)
        extra = _ring_section(events) + _overlap_remat_sections(events)
        if extra:
            return "\n".join(extra)
        return "comm efficiency: no Comm/* events in this file"
    lines = [f"comm efficiency ({n_steps} steps)"]
    lines.append(f"  {'op':<28} {'count/step':>10} {'bytes/step':>14} "
                 f"{'algo bytes/step':>16}")
    tot_count = tot_bytes = tot_algo = 0.0
    for op, kinds in sorted(per_op.items()):
        count = kinds.get("count", 0.0)
        nbytes = kinds.get("bytes", 0.0)
        algo = kinds.get("algo_bytes", nbytes)
        tot_count += count
        tot_bytes += nbytes
        tot_algo += algo
        lines.append(f"  {op:<28} {int(count):>10} "
                     f"{_fmt_bytes(nbytes):>14} {_fmt_bytes(algo):>16}")
    lines.append(f"  {'TOTAL':<28} {int(tot_count):>10} "
                 f"{_fmt_bytes(tot_bytes):>14} {_fmt_bytes(tot_algo):>16}")
    lines.append("")
    lines.append(f"  collectives/step:      {int(tot_count)}")
    lines.append(f"  algo bytes/step:       {_fmt_bytes(tot_algo)}")
    lines.append(f"  algo bytes whole run:  {_fmt_bytes(tot_algo * n_steps)}")
    busbw = [e["value"] for e in events
             if e["name"] == "Comm/total/busbw_gbps"]
    if busbw:
        lines.append(f"  busbw (last):          {busbw[-1]:.2f} GB/s")
    frac = [e["value"] for e in events
            if e["name"] == "Comm/total/est_comm_frac"]
    if frac:
        lines.append(f"  est unoverlapped comm: {frac[-1] * 100:.1f}% "
                     f"of step time (upper bound)")
    quant = _quantized_comm_section(per_op, events)
    if quant:
        lines.append("")
        lines.extend(quant)
    ring = _ring_section(events)
    if ring:
        lines.append("")
        lines.extend(ring)
    extra = _overlap_remat_sections(events)
    if extra:
        lines.append("")
        lines.extend(extra)
    return "\n".join(lines)


def _ring_section(events: List[dict]) -> List[str]:
    """Ring-attention schedule rollup (``Comm/ring/*`` — sequence/ring.py,
    docs/performance.md "Million-token context"): KV-rotation hops/bytes,
    the active layout/overlap knobs, the measured compute↔transfer overlap
    fraction, and the silent-dense-fallback marker (nonzero = a ring entry
    point ran WITHOUT a seq axis and silently densified — fix the mesh)."""
    ring: Dict[str, float] = {}
    for e in events:
        if e["name"].startswith("Comm/ring/"):
            ring[e["name"].rsplit("/", 1)[-1]] = e["value"]  # last wins
    if not ring:
        return []
    lines = ["ring attention (Comm/ring/*)"]
    if "hops" in ring:
        lines.append(f"  KV-rotation hops:      {int(ring['hops'])}")
    if "bytes" in ring:
        lines.append(f"  KV bytes rotated:      {_fmt_bytes(ring['bytes'])}")
    if "zigzag" in ring:
        layout = "zigzag" if ring["zigzag"] else "contiguous"
        lines.append(f"  causal layout:         {layout}")
    if "overlap_on" in ring:
        lines.append(f"  overlap pipelining:    "
                     f"{'on' if ring['overlap_on'] else 'off'}")
    if "overlap_frac" in ring:
        lines.append(f"  measured overlap:      "
                     f"{ring['overlap_frac'] * 100:.1f}% of transfer hidden "
                     f"under compute")
    if ring.get("dense_fallback"):
        lines.append(f"  DENSE FALLBACK:        {int(ring['dense_fallback'])} "
                     f"call(s) ran without a seq axis (no ring executed)")
    return lines


def _quantized_comm_section(per_op: Dict[str, Dict[str, float]],
                            events: List[dict]) -> List[str]:
    """Quantized & hierarchical collectives rollup (ZeRO++ qwZ/qgZ/hpZ +
    EQuARX — docs/performance.md): per-path bytes-on-wire vs the fp32
    equivalent of the same payload (``Comm/<op>/fp32_equiv_bytes``) with the
    resulting compression ratio, plus the DCN-vs-ICI byte split from the
    per-collective link-class tag. Only rendered when at least one path
    actually compressed (ratio > 1.05) or a DCN split exists."""
    rows = []
    for op, kinds in sorted(per_op.items()):
        wire = kinds.get("bytes", 0.0)
        equiv = kinds.get("fp32_equiv_bytes", 0.0)
        if wire > 0 and equiv > wire * 1.05:
            rows.append((op, wire, equiv, equiv / wire))
    dcn = [e["value"] for e in events
           if e["name"] == "Comm/total/algo_bytes_dcn"]
    ici = [e["value"] for e in events
           if e["name"] == "Comm/total/algo_bytes_ici"]
    if not dcn:  # fall back to the per-op link split
        s = sum(k.get("algo_bytes_dcn", 0.0) for k in per_op.values())
        dcn = [s] if s else []
        ici = [sum(k.get("algo_bytes_ici", 0.0) for k in per_op.values())]
    has_dcn = bool(dcn and dcn[-1] > 0)
    if not rows and not has_dcn:
        return []
    lines = ["quantized & hierarchical collectives"]
    if rows:
        lines.append(f"  {'path':<28} {'wire bytes':>14} {'fp32 equiv':>14} "
                     f"{'ratio':>7}")
        for op, wire, equiv, ratio in rows:
            lines.append(f"  {op:<28} {_fmt_bytes(wire):>14} "
                         f"{_fmt_bytes(equiv):>14} {ratio:>6.2f}x")
    if dcn:
        total = (dcn[-1] if dcn else 0.0) + (ici[-1] if ici else 0.0)
        pct = dcn[-1] / total * 100 if total else 0.0
        lines.append(f"  DCN algo bytes/step:   {_fmt_bytes(dcn[-1])} "
                     f"({pct:.1f}% of total)")
        if ici:
            lines.append(f"  ICI algo bytes/step:   {_fmt_bytes(ici[-1])}")
    return lines


def _overlap_remat_sections(events: List[dict]) -> List[str]:
    """Fine-grained overlap + selective-remat + native-GQA rollup (the
    ``Train/overlap/*``, ``Train/remat/*`` and ``Train/attn/*`` gauge
    series — docs/performance.md): layer-prefetch configuration,
    overlap-hidden comm fraction, the per-remat-policy saved-bytes /
    peak-HBM / step-time sweep rows, and the narrow-KV attention traffic
    accounting. Gauges: last sample per series wins."""
    ov = {e["name"][len("Train/overlap/"):]: e["value"] for e in events
          if e["name"].startswith("Train/overlap/")}
    remat = {e["name"][len("Train/remat/"):]: e["value"] for e in events
             if e["name"].startswith("Train/remat/")}
    attn = {e["name"][len("Train/attn/"):]: e["value"] for e in events
            if e["name"].startswith("Train/attn/")}
    lines: List[str] = []
    if attn:
        lines.append("native GQA attention (attention.gqa_native)")
        if "gqa_ratio" in attn:
            lines.append(f"  query/kv head ratio:   "
                         f"{attn['gqa_ratio']:.0f}x")
        if "kv_bytes_saved" in attn:
            lines.append(f"  KV bytes saved/step:   "
                         f"{_fmt_bytes(attn['kv_bytes_saved'])} "
                         f"(fwd+bwd, vs widened kernels)")
        lines.append("")
    if ov:
        lines.append("fine-grained overlap (layer prefetch)")
        if "prefetch_depth" in ov:
            lines.append(f"  prefetch depth:        "
                         f"{int(ov['prefetch_depth'])} layer(s) in flight")
        if "prefetch_layers" in ov:
            lines.append(f"  prefetched layers:     "
                         f"{int(ov['prefetch_layers'])} per step")
        if "prefetch_bytes" in ov:
            lines.append(f"  gathered bytes/step:   "
                         f"{_fmt_bytes(ov['prefetch_bytes'])}")
        if "hidden_comm_frac" in ov:
            lines.append(f"  overlap-hidden comm:   "
                         f"{ov['hidden_comm_frac'] * 100:.1f}% of serial "
                         f"comm time (lower bound)")
    if remat:
        # names are <metric>_<policy>; metrics are fixed, policies open-ended
        per_policy: Dict[str, Dict[str, float]] = {}
        for key, val in remat.items():
            for metric in ("saved_bytes", "peak_bytes", "step_ms"):
                if key.startswith(metric + "_"):
                    per_policy.setdefault(key[len(metric) + 1:],
                                          {})[metric] = val
                    break
        if per_policy:
            if lines:
                lines.append("")
            lines.append("selective remat sweep (per policy)")
            lines.append(f"  {'policy':<22} {'saved bytes':>14} "
                         f"{'peak HBM':>14} {'step ms':>10}")
            for pol, m in sorted(per_policy.items()):
                saved = (_fmt_bytes(m["saved_bytes"])
                         if "saved_bytes" in m else "-")
                peak = (_fmt_bytes(m["peak_bytes"])
                        if "peak_bytes" in m else "-")
                step = (f"{m['step_ms']:.2f}" if "step_ms" in m else "-")
                lines.append(f"  {pol:<22} {saved:>14} {peak:>14} "
                             f"{step:>10}")
    return lines


def _startup_block(proc: Dict[str, float],
                   per: Dict[str, Dict[str, float]]) -> List[str]:
    """Where start-up went: the process-wide compile account
    (``Compile/process/*``: every program JAX traced, lowered or compiled,
    seconds as the union of the intervals) and the ten registered programs
    that cost most (lowering + compile + the monitor's own analysis)."""
    lines = ["", "start-up, whole process (jax.monitoring)"]
    for key, label in (("trace_lower_s", "tracing + lowering"),
                       ("backend_compile_s", "backend compile"),
                       ("cache_retrieval_s", "  of it cache retrieval"),
                       ("monitor_analysis_s", "monitor's own analysis")):
        lines.append(f"  {label + ':':<26} {proc.get(key, 0.0):>9.2f} s")
    lines.append(
        f"  persistent cache:          "
        f"{int(proc.get('cache_hits', 0))} hits, "
        f"{int(proc.get('cache_misses', 0))} misses of "
        f"{int(proc.get('cache_requests', 0))} requests; "
        f"{int(proc.get('programs_compiled', 0))} programs asked of the "
        f"backend")
    cost = lambda m: (m.get("lower_ms", 0.0) + m.get("compile_ms", 0.0)
                      + m.get("analysis_ms", 0.0))
    lines.append(f"  {'costliest programs':<18} {'lower ms':>10} "
                 f"{'compile ms':>11} {'analysis ms':>12} {'disk cache':>14}")
    for prog in sorted(per, key=lambda p: -cost(per[p]))[:10]:
        m = per[prog]
        disk = (f"{int(m.get('persistent_cache_hits', 0))} hit "
                f"{int(m.get('persistent_cache_misses', 0))} miss")
        lines.append(
            f"  {prog:<18} {m.get('lower_ms', 0.0):>10.1f} "
            f"{m.get('compile_ms', 0.0):>11.1f} "
            f"{m.get('analysis_ms', 0.0):>12.1f} {disk:>14}")
    return lines


def compile_report(events: List[dict]) -> str:
    """``--compile``: recompilation-sentinel counters per jitted program
    (compiles, cache hits, RECOMPILES, lowering/compile wall time, analytic
    cost-model flops) from the ``Compile/*`` stream, where start-up went
    (``Compile/process/*``: :func:`_startup_block`), plus the per-program
    MFU attribution from ``Train/mfu/*`` / ``Serving/mfu/*`` — the
    decomposition of the ThroughputTimer headline (docs/observability.md).
    Cumulative counters and gauges: last sample per series wins."""
    comp = [e for e in events if e["name"].startswith("Compile/")]
    mfu = [e for e in events
           if e["name"].startswith(("Train/mfu/", "Serving/mfu/"))]
    if not comp and not mfu:
        return "compile: no Compile/* or */mfu/* events in this file"
    lines: List[str] = []
    if comp:
        per: Dict[str, Dict[str, float]] = {}
        for e in comp:
            _, prog, metric = e["name"].split("/", 2)
            per.setdefault(prog, {})[metric] = e["value"]   # last wins
        tot = per.pop("total", {})
        proc = per.pop("process", {})
        lines.append(f"compile report ({len(comp)} events)")
        lines.append(f"  {'program':<18} {'compiles':>8} {'hits':>8} "
                     f"{'recompiles':>10} {'compile ms':>11} "
                     f"{'cost flops':>12}")
        for prog in sorted(per):
            m = per[prog]
            fl = m.get("cost_flops", 0.0)
            fl_s = f"{fl:>12.3e}" if fl else f"{'-':>12}"
            lines.append(
                f"  {prog:<18} {int(m.get('compiles', 0)):>8} "
                f"{int(m.get('cache_hits', 0)):>8} "
                f"{int(m.get('recompiles', 0)):>10} "
                f"{m.get('compile_ms', 0.0):>11.1f} {fl_s}")
        lines.append("")
        recompiles = int(tot.get("recompiles", 0))
        lines.append(f"  programs:               "
                     f"{int(tot.get('programs', len(per)))}")
        lines.append(f"  total compiles:         "
                     f"{int(tot.get('compiles', 0))}")
        lines.append(f"  total recompiles:       {recompiles}"
                     + ("  <-- recompilation storm suspect"
                        if recompiles > int(tot.get("programs", 0)) else ""))
        lines.append(f"  compile wall time:      "
                     f"{tot.get('compile_ms', 0.0) / 1e3:.2f} s "
                     f"(+ {tot.get('lower_ms', 0.0) / 1e3:.2f} s lowering)")
        if proc:
            lines += _startup_block(proc, per)
    if mfu:
        last: Dict[str, float] = {}
        for e in mfu:
            last[e["name"]] = e["value"]                     # last wins
        if lines:
            lines.append("")
        lines.append("per-program MFU attribution (fraction of peak)")
        total = last.pop("Train/mfu/total", None)
        headline = last.pop("Train/mfu/headline", None)
        for name in sorted(last):
            prog = name.split("/", 2)[2]
            group = name.split("/", 1)[0].lower()
            lines.append(f"  {group + '/' + prog:<26} {last[name]:>8.4f}")
        if total is not None:
            lines.append(f"  {'TOTAL (attributed)':<26} {total:>8.4f}")
        if headline is not None:
            lines.append(f"  {'ThroughputTimer headline':<26} "
                         f"{headline:>8.4f}")
        if total and headline:
            lines.append(f"  attribution covers      "
                         f"{total / headline * 100:.1f}% of the headline")
    return "\n".join(lines)


def _load_anomaly_module():
    """Load ``deepspeed_tpu/telemetry/anomaly.py`` by file path (it is
    stdlib-only) so the offline replay needs no jax/numpy import; None when
    the report runs detached from the repo tree."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "deepspeed_tpu", "telemetry", "anomaly.py")
    try:
        spec = importlib.util.spec_from_file_location("_dstpu_anomaly", path)
        mod = importlib.util.module_from_spec(spec)
        # dataclass construction resolves string annotations through
        # sys.modules — a by-path module must be registered first
        sys.modules["_dstpu_anomaly"] = mod
        spec.loader.exec_module(mod)
        return mod
    except Exception:
        sys.modules.pop("_dstpu_anomaly", None)
        return None


def anomalies(events: List[dict]) -> str:
    """``--anomalies``: live ``Anomaly/*`` findings recorded by the hub's
    detector (spikes, drift, stragglers — count, worst excess, last step),
    plus an OFFLINE replay of the same rolling-median/MAD detector over the
    file's ``Train/Step/*_ms`` series, so a run recorded without the
    detector enabled can still be screened post-hoc."""
    rec = [e for e in events if e["name"].startswith("Anomaly/")]
    lines: List[str] = []
    if rec:
        per: Dict[str, Dict[str, float]] = {}
        for e in rec:
            d = per.setdefault(e["name"][len("Anomaly/"):],
                               {"count": 0, "worst": 0.0, "last_step": 0})
            d["count"] += 1
            d["worst"] = max(d["worst"], float(e["value"]))
            d["last_step"] = max(d["last_step"], int(e.get("step", 0)))
        lines.append(f"anomaly report ({len(rec)} recorded findings)")
        lines.append(f"  {'finding':<28} {'count':>6} {'worst excess':>13} "
                     f"{'last step':>10}")
        for key in sorted(per):
            d = per[key]
            lines.append(f"  {key:<28} {d['count']:>6} "
                         f"{d['worst'] * 100:>12.0f}% {d['last_step']:>10}")
    else:
        lines.append("anomaly report: no recorded Anomaly/* findings")
    mod = _load_anomaly_module()
    phase = OrderedDict()
    for e in events:
        n = e["name"]
        if n.startswith("Train/Step/") and n.endswith("_ms"):
            phase.setdefault(n[len("Train/Step/"):-len("_ms")],
                             []).append(e)
    if mod is None:
        lines.append("  (offline replay unavailable: telemetry/anomaly.py "
                     "not found next to this script)")
        return "\n".join(lines)
    if not phase:
        lines.append("  (no Train/Step/*_ms series to replay — record with "
                     "wall_clock_breakdown: true)")
        return "\n".join(lines)
    det = mod.AnomalyDetector(mod.AnomalyConfig(enabled=True))
    findings = []
    for key, recs in phase.items():
        series = "step_time" if key == "train_batch" else f"phase/{key}"
        for r in recs:
            findings += det.observe(series, float(r["value"]),
                                    int(r.get("step", 0)))
    n_samples = sum(len(v) for v in phase.values())
    lines.append("")
    lines.append(f"offline replay over {len(phase)} step-time series "
                 f"({n_samples} samples): {len(findings)} finding(s)")
    for f in findings[:20]:
        lines.append(f"  [{f.series}] {f.detail}")
    if len(findings) > 20:
        lines.append(f"  ... {len(findings) - 20} more")
    return "\n".join(lines)


def reliability(events: List[dict]) -> str:
    """``--reliability``: skipped steps, watchdog events, and checkpoint
    save/restore/rollback counts from the ``Reliability/*`` event stream
    (reliability subsystem — docs/reliability.md). Each event is one
    occurrence; counts are event-line counts, not value sums."""
    rel = [e for e in events if e["name"].startswith("Reliability/")]
    if not rel:
        return "reliability: no Reliability/* events in this file"
    counts: Dict[str, int] = {}
    last_step: Dict[str, int] = {}
    for e in rel:
        key = e["name"][len("Reliability/"):]
        counts[key] = counts.get(key, 0) + 1
        last_step[key] = max(last_step.get(key, 0), int(e.get("step", 0)))
    lines = [f"reliability report ({len(rel)} events)"]
    lines.append(f"  {'event':<28} {'count':>6} {'last step':>10}")
    for key in sorted(counts):
        lines.append(f"  {key:<28} {counts[key]:>6} {last_step[key]:>10}")
    lines.append("")

    def total(*keys: str) -> int:
        return sum(counts.get(k, 0) for k in keys)

    violations = total(*[k for k in counts if k.startswith("violation/")])
    lines.append(f"  checkpoint saves:       {total('checkpoint_saved')}")
    lines.append(f"  checkpoint loads:       {total('checkpoint_loaded')}")
    lines.append(f"  rollbacks (walk-back):  {total('checkpoint_rollback')}")
    lines.append(f"  auto-restores:          {total('auto_restore')}")
    lines.append(f"  I/O retries:            {total('checkpoint_io_retry')}")
    lines.append(f"  GC'd old tags:          {total('checkpoint_gc')}")
    lines.append(f"  overflow-skipped steps: {total('overflow_skip')}")
    lines.append(f"  loss spikes:            {total('loss_spike')}")
    lines.append(f"  stall warnings:         {total('stall_warning')}")
    lines.append(f"  watchdog violations:    {violations}")
    lines.append(f"  preemption checkpoints: {total('preemption_checkpoint')}")
    # elastic training runtime (Reliability/elastic/* — the closed registry
    # in telemetry/schema.py; docs/reliability.md "Elastic training &
    # universal checkpoint")
    if any(k.startswith("elastic/") for k in counts):
        lines.append("")
        lines.append("  elastic runtime:")
        lines.append(f"    universal saves:      {total('elastic/saves')}")
        lines.append(f"    elastic resumes:      {total('elastic/resumes')}")
        lines.append(f"    topology reshards:    {total('elastic/reshards')}")
        lines.append(f"    host losses detected: "
                     f"{total('elastic/host_loss_detected')}")
        lines.append(f"    drill passes:         "
                     f"{total('elastic/drill_pass')}")
    # numerics-integrity plane (Reliability/integrity/* — the closed
    # registry in telemetry/schema.py; docs/reliability.md "Numerics
    # integrity & SDC")
    if any(k.startswith("integrity/") for k in counts):
        checks = total("integrity/checks")
        mism = total("integrity/mismatches")
        lines.append("")
        lines.append("  numerics integrity:")
        lines.append(f"    fingerprint checks:   {checks}")
        lines.append(f"    shadow audits:        {total('integrity/audit_steps')}")
        lines.append(f"    mismatches:           {mism}"
                     + (f" ({mism / checks:.2%} of checks)" if checks else ""))
        lines.append(f"    host attributions:    "
                     f"{total('integrity/attributed_host')}")
        lines.append(f"    quarantines:          "
                     f"{total('integrity/quarantines')}")
        lines.append(f"    checkpoint walk-backs:"
                     f" {total('integrity/walkbacks')}")
    return "\n".join(lines)


def memory_report(events: List[dict]) -> str:
    """``--memory``: the tiered memory subsystem's ``Memory/tier/*`` stream
    (docs/memory.md) — per-tier resident bytes, transfer volume and the
    measured compute-overlap fraction, prefetch hit/miss, and the serving
    KV host-spill pool occupancy — plus the open ``Memory/{bytes_in_use,
    peak_bytes}`` allocator gauges. Tier series carry gauge/cumulative
    values, so the last sample per series is current."""
    tier = [e for e in events if e["name"].startswith("Memory/tier/")]
    alloc = [e for e in events if e["name"].startswith("Memory/")
             and not e["name"].startswith("Memory/tier/")]
    if not tier and not alloc:
        return "memory: no Memory/* events in this file"
    lines = []

    def last(evs: List[dict], name: str) -> float:
        vals = [e["value"] for e in evs if e["name"] == name]
        return float(vals[-1]) if vals else 0.0

    if tier:
        t = lambda m: last(tier, f"Memory/tier/{m}")  # noqa: E731
        lines.append(f"tiered memory ({len(tier)} Memory/tier/* events)")
        lines.append(f"  host tier resident:   "
                     f"{_fmt_bytes(t('resident_bytes_host'))}")
        lines.append(f"  file tier resident:   "
                     f"{_fmt_bytes(t('resident_bytes_file'))}")
        lines.append(f"  transfers:            "
                     f"{_fmt_bytes(t('transfer_d2h_bytes'))} D2H / "
                     f"{_fmt_bytes(t('transfer_h2d_bytes'))} H2D "
                     f"({t('offloads'):.0f} offloads, "
                     f"{t('restores'):.0f} restores)")
        busy, ov = t("transfer_busy_ms"), t("overlap_ms")
        lines.append(f"  transfer wall time:   {busy:.1f} ms "
                     f"({ov:.1f} ms hidden under compute → "
                     f"overlap_frac {t('overlap_frac'):.2f})")
        hits, misses = t("prefetch_hits"), t("prefetch_misses")
        tot = hits + misses
        lines.append(f"  prefetch:             {hits:.0f} hits / "
                     f"{misses:.0f} misses"
                     + (f" ({hits / tot:.1%} fully hidden)" if tot else ""))
        if any(e["name"].startswith("Memory/tier/kv_") for e in tier):
            lines.append(f"  KV host-spill pool:   "
                         f"{t('kv_spilled_blocks'):.0f} blocks "
                         f"({_fmt_bytes(t('kv_spilled_bytes'))}); "
                         f"{t('kv_spills'):.0f} spills, "
                         f"{t('kv_restores'):.0f} restores")
    if alloc:
        if tier:
            lines.append("")
        lines.append(f"device allocator")
        lines.append(f"  bytes in use:         "
                     f"{_fmt_bytes(last(alloc, 'Memory/bytes_in_use'))}")
        lines.append(f"  peak bytes:           "
                     f"{_fmt_bytes(last(alloc, 'Memory/peak_bytes'))}")
    return "\n".join(lines)


def serving(events: List[dict]) -> str:
    """``--serving``: prefix-cache hit-rate, prefill tokens saved, retained-
    pool occupancy and evictions from the ``Serving/prefix_cache/*`` stream,
    the speculative-decoding efficiency counters from ``Serving/spec/*``,
    the continuous-batching scheduler counters from ``Serving/sched/*``
    (queue depth, admitted/rejected/preempted, queue-wait percentiles,
    goodput-under-SLO), the multi-replica router placement counters from
    ``Serving/router/*``, and the fleet-resilience counters from
    ``Serving/fleet/*`` (failovers, replayed tokens, circuit-breaker
    transitions, shed requests, degradation level — docs/serving.md), and
    the quantized-KV-cache gauges from ``Serving/kv_quant/*`` (resident
    quantized blocks, bytes saved vs bf16, dequant-error bound, fused-
    dequant flag — docs/serving.md "Quantized KV cache"), and the
    disaggregated prefill/decode counters from ``Serving/disagg/*``
    (handoffs, wire bytes vs bf16-equivalent, chain-hash dedup savings —
    docs/serving.md "Disaggregated prefill/decode"). These
    series carry CUMULATIVE counter values (gauges for occupancy/rates), so
    the last sample per series is the run total — unlike
    ``--reliability``'s one-line-per-occurrence."""
    srv = [e for e in events if e["name"].startswith("Serving/prefix_cache/")]
    spec = [e for e in events if e["name"].startswith("Serving/spec/")]
    sched = [e for e in events if e["name"].startswith("Serving/sched/")]
    router = [e for e in events if e["name"].startswith("Serving/router/")]
    fleet = [e for e in events if e["name"].startswith("Serving/fleet/")]
    kvq = [e for e in events if e["name"].startswith("Serving/kv_quant/")]
    disagg = [e for e in events if e["name"].startswith("Serving/disagg/")]
    if not srv and not spec and not sched and not router and not fleet \
            and not kvq and not disagg:
        return ("serving: no Serving/{prefix_cache,spec,sched,router,fleet,"
                "kv_quant,disagg}/* events in this file")
    lines: List[str] = []
    if kvq:
        kq: Dict[str, float] = {}
        for e in kvq:
            kq[e["name"][len("Serving/kv_quant/"):]] = e["value"]  # last wins
        lines.append(f"KV quantization report ({len(kvq)} events)")
        lines.append(f"  quantized blocks (now): "
                     f"{kq.get('blocks_quantized', 0):,.0f}")
        lines.append(f"  bytes saved vs bf16:    "
                     f"{_fmt_bytes(kq.get('bytes_saved', 0))}")
        lines.append(f"  max abs dequant error:  "
                     f"{kq.get('max_abs_err', 0):.6f} (<= scale/2 bound)")
        fused = kq.get("dequant_fused", 0) >= 1.0
        lines.append(f"  dequant fused in-kernel: {'yes' if fused else 'NO'}"
                     + ("" if fused else
                        "  <-- standalone int8 casts LOSE on the MXU "
                        "(QUANT_TPU_LIVE.json)"))
    if srv:
        if lines:
            lines.append("")
        last: Dict[str, float] = {}
        last_step: Dict[str, int] = {}
        for e in srv:
            key = e["name"][len("Serving/prefix_cache/"):]
            last[key] = e["value"]                   # cumulative: last wins
            last_step[key] = max(last_step.get(key, 0), int(e.get("step", 0)))
        lines.append(f"serving prefix-cache report ({len(srv)} events)")
        lines.append(f"  {'counter':<24} {'total':>14} {'last step':>10}")
        for key in sorted(last):
            lines.append(f"  {key:<24} {last[key]:>14,.0f} "
                         f"{last_step[key]:>10}")
        lines.append("")
        lookups = last.get("lookups", 0.0)
        hits = last.get("hits", 0.0)
        lines.append(f"  admissions (lookups):   {lookups:,.0f}")
        lines.append(f"  prefix hits:            {hits:,.0f}")
        lines.append(f"  hit rate:               "
                     f"{hits / lookups * 100 if lookups else 0.0:.1f}%")
        lines.append(f"  hit tokens:             "
                     f"{last.get('hit_tokens', 0):,.0f}")
        lines.append(f"  prefill tokens saved:   "
                     f"{last.get('prefill_tokens_saved', 0):,.0f}")
        lines.append(f"  copy-on-write copies:   "
                     f"{last.get('cow_copies', 0):,.0f}")
        lines.append(f"  evictions:              "
                     f"{last.get('evictions', 0):,.0f}")
        lines.append(f"  retained blocks (now):  "
                     f"{last.get('retained_blocks', 0):,.0f}")
    if spec:
        if lines:
            lines.append("")
        sp: Dict[str, float] = {}
        for e in spec:
            sp[e["name"][len("Serving/spec/"):]] = e["value"]  # last wins
        lines.append(f"speculative decoding report ({len(spec)} events)")
        steps = sp.get("verify_steps", 0.0) + sp.get("decode_steps", 0.0)
        lines.append(f"  model steps:            {steps:,.0f} "
                     f"({sp.get('verify_steps', 0):,.0f} verify, "
                     f"{sp.get('decode_steps', 0):,.0f} plain decode)")
        lines.append(f"  drafted tokens:         "
                     f"{sp.get('drafted_tokens', 0):,.0f}")
        lines.append(f"  accepted tokens:        "
                     f"{sp.get('accepted_tokens', 0):,.0f}")
        lines.append(f"  rolled-back tokens:     "
                     f"{sp.get('rolled_back_tokens', 0):,.0f}")
        lines.append(f"  emitted tokens:         "
                     f"{sp.get('emitted_tokens', 0):,.0f}")
        lines.append(f"  accept rate:            "
                     f"{sp.get('accept_rate', 0) * 100:.1f}%")
        lines.append(f"  mean accepted length:   "
                     f"{sp.get('mean_accepted_len', 0):.2f} tok/verify")
        lines.append(f"  tokens per model step:  "
                     f"{sp.get('tokens_per_step', 0):.2f} per sequence")
        lines.append(f"  verify batch occupancy: "
                     f"{sp.get('verify_batch_occupancy', 0) * 100:.1f}%")
    if sched:
        if lines:
            lines.append("")
        sc: Dict[str, float] = {}
        for e in sched:
            sc[e["name"][len("Serving/sched/"):]] = e["value"]  # last wins
        lines.append(f"scheduler report ({len(sched)} events)")
        lines.append(f"  submitted:              {sc.get('submitted', 0):,.0f}"
                     f"  (admitted {sc.get('admitted', 0):,.0f}, chunked "
                     f"{sc.get('chunked_admissions', 0):,.0f}, rejected "
                     f"{sc.get('rejected', 0):,.0f}, expired "
                     f"{sc.get('expired', 0):,.0f})")
        lines.append(f"  preempted / resumed:    "
                     f"{sc.get('preempted', 0):,.0f} / "
                     f"{sc.get('resumed', 0):,.0f}")
        lines.append(f"  completed:              "
                     f"{sc.get('completed', 0):,.0f}  (SLO met "
                     f"{sc.get('slo_met', 0):,.0f}, missed "
                     f"{sc.get('slo_missed', 0):,.0f})")
        lines.append(f"  goodput under SLO:      "
                     f"{sc.get('goodput_frac', 0) * 100:.1f}% of completions"
                     f"  ({sc.get('goodput_rps', 0):.2f} req/s)")
        lines.append(f"  queue depth (now):      "
                     f"{sc.get('queue_depth', 0):,.0f}")
        lines.append(f"  queue wait ms p50/p90/p99: "
                     f"{sc.get('queue_wait_ms_p50', 0):.2f} / "
                     f"{sc.get('queue_wait_ms_p90', 0):.2f} / "
                     f"{sc.get('queue_wait_ms_p99', 0):.2f}"
                     f"  ({sc.get('queue_wait_ms_count', 0):,.0f} samples)")
        lines.append(f"  scheduler ticks:        {sc.get('ticks', 0):,.0f}"
                     f"  ({sc.get('tokens_emitted', 0):,.0f} tokens "
                     f"emitted)")
    if router:
        if lines:
            lines.append("")
        rt: Dict[str, float] = {}
        for e in router:
            rt[e["name"][len("Serving/router/"):]] = e["value"]  # last wins
        lines.append(f"router report ({len(router)} events)")
        reqs = rt.get("requests", 0.0)
        lines.append(f"  requests routed:        {reqs:,.0f} across "
                     f"{rt.get('replicas', 0):,.0f} active replicas")
        aff_pct = rt.get("affinity_hits", 0) / reqs * 100 if reqs else 0.0
        lines.append(f"  prefix-affinity hits:   "
                     f"{rt.get('affinity_hits', 0):,.0f}  "
                     f"({aff_pct:.1f}% of placements)")
        lines.append(f"  session-sticky hits:    "
                     f"{rt.get('session_hits', 0):,.0f}")
        lines.append(f"  load fallbacks:         "
                     f"{rt.get('load_fallbacks', 0):,.0f}")
        lines.append(f"  admission fallbacks:    "
                     f"{rt.get('reject_fallbacks', 0):,.0f}")
        lines.append(f"  drains:                 {rt.get('drains', 0):,.0f}")
    if fleet:
        if lines:
            lines.append("")
        fl: Dict[str, float] = {}
        for e in fleet:
            fl[e["name"][len("Serving/fleet/"):]] = e["value"]  # last wins
        lines.append(f"fleet resilience report ({len(fleet)} events)")
        lines.append(f"  failovers:              "
                     f"{fl.get('failovers', 0):,.0f}  "
                     f"({fl.get('replayed_tokens', 0):,.0f} tokens replayed)")
        lines.append(f"  tick faults:            "
                     f"{fl.get('tick_faults', 0):,.0f}  (slow ticks "
                     f"{fl.get('slow_ticks', 0):,.0f}, probes "
                     f"{fl.get('probe_ticks', 0):,.0f})")
        lines.append(f"  circuit transitions:    "
                     f"{fl.get('circuit_open', 0):,.0f} open / "
                     f"{fl.get('circuit_half_open', 0):,.0f} half-open / "
                     f"{fl.get('circuit_closed', 0):,.0f} closed")
        lines.append(f"  shed requests:          "
                     f"{fl.get('shed_requests', 0):,.0f}")
        lines.append(f"  degrade level (now):    "
                     f"{fl.get('degrade_level', 0):,.0f}  "
                     f"({fl.get('degrade_shifts', 0):,.0f} shifts)")
        lines.append(f"  broken replicas (now):  "
                     f"{fl.get('broken_replicas', 0):,.0f}")
    if disagg:
        if lines:
            lines.append("")
        dg: Dict[str, float] = {}
        for e in disagg:
            dg[e["name"][len("Serving/disagg/"):]] = e["value"]  # last wins
        lines.append(f"disaggregation report ({len(disagg)} events)")
        lines.append(f"  tiers:                  "
                     f"{dg.get('prefill_replicas', 0):,.0f} prefill / "
                     f"{dg.get('decode_replicas', 0):,.0f} decode")
        lines.append(f"  kv handoffs:            "
                     f"{dg.get('handoffs', 0):,.0f}  "
                     f"({dg.get('blocks_shipped', 0):,.0f} blocks shipped)")
        lines.append(f"  wire bytes:             "
                     f"{_fmt_bytes(dg.get('wire_bytes', 0))} of "
                     f"{_fmt_bytes(dg.get('bf16_equiv_bytes', 0))} "
                     f"bf16-equiv ({dg.get('wire_ratio', 0):.3f}x)")
        lines.append(f"  dedup (chain-hash):     "
                     f"{dg.get('dedup_blocks', 0):,.0f} blocks off the wire "
                     f"({_fmt_bytes(dg.get('dedup_bytes_saved', 0))} saved)")
        lines.append(f"  import drops/failures:  "
                     f"{dg.get('import_dropped', 0):,.0f} / "
                     f"{dg.get('import_failures', 0):,.0f}")
        lines.append(f"  tier fallbacks:         "
                     f"{dg.get('tier_fallbacks', 0):,.0f} admission / "
                     f"{dg.get('handoff_fallbacks', 0):,.0f} handoff")
    return "\n".join(lines)


def latency(events: List[dict]) -> str:
    """``--latency``: request-latency SLO percentiles from the
    ``Serving/latency/*`` stream (TTFT, inter-token latency, queue time,
    e2e — docs/serving.md). These are gauges: the last sample per series is
    the run's value."""
    lat = [e for e in events if e["name"].startswith("Serving/latency/")]
    if not lat:
        return "latency: no Serving/latency/* events in this file"
    last: Dict[str, float] = {}
    for e in lat:
        last[e["name"][len("Serving/latency/"):]] = e["value"]
    metrics = sorted({k.rsplit("_", 1)[0] for k in last})
    lines = [f"serving latency SLOs ({len(lat)} events)"]
    lines.append(f"  {'metric':<12} {'count':>7} {'p50':>10} {'p90':>10} "
                 f"{'p99':>10}")
    for m in metrics:
        lines.append(
            f"  {m:<12} {last.get(m + '_count', 0):>7,.0f} "
            f"{last.get(m + '_p50', 0):>10.2f} "
            f"{last.get(m + '_p90', 0):>10.2f} "
            f"{last.get(m + '_p99', 0):>10.2f}")
    lines.append("")
    lines.append("  (ms; ttft = time to first token, itl = inter-token "
                 "latency, queue = admit→first compute, e2e = admit→finish)")
    return "\n".join(lines)


def trace_report(path: str) -> str:
    """``--trace <out.json>``: summarize a Chrome-trace / Perfetto JSON file
    (a flight-recorder dump): span counts + total/mean duration per name,
    the slowest individual spans, and instant-event counts."""
    with open(path) as f:
        doc = json.load(f)
    evs = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    spans = [e for e in evs if e.get("ph") == "X"]
    instants = [e for e in evs if e.get("ph") in ("i", "I")]
    meta = doc.get("otherData", {}) if isinstance(doc, dict) else {}
    lines = [f"trace report: {len(spans)} spans, {len(instants)} instants"
             + (f" (dump reason: {meta['reason']})" if meta.get("reason")
                else "")]
    if not spans and not instants:
        return lines[0]
    per: Dict[str, List[float]] = {}
    for e in spans:
        per.setdefault(e.get("name", "?"), []).append(float(e.get("dur", 0)))
    if per:
        lines.append("")
        lines.append(f"  {'span':<28} {'count':>6} {'total ms':>10} "
                     f"{'mean ms':>10} {'max ms':>10}")
        for name, durs in sorted(per.items(),
                                 key=lambda kv: -sum(kv[1])):
            lines.append(f"  {name:<28} {len(durs):>6} "
                         f"{sum(durs) / 1e3:>10.2f} "
                         f"{sum(durs) / len(durs) / 1e3:>10.3f} "
                         f"{max(durs) / 1e3:>10.3f}")
    top = sorted(spans, key=lambda e: -float(e.get("dur", 0)))[:5]
    if top:
        lines.append("")
        lines.append("  slowest spans:")
        for e in top:
            args = e.get("args", {})
            extras = ", ".join(f"{k}={v}" for k, v in args.items()
                               if k not in ("trace_id", "span_id",
                                            "parent_id"))
            lines.append(f"    {e.get('name', '?'):<24} "
                         f"{float(e.get('dur', 0)) / 1e3:>9.3f} ms"
                         + (f"  ({extras})" if extras else ""))
    if instants:
        per_i: Dict[str, int] = {}
        for e in instants:
            per_i[e.get("name", "?")] = per_i.get(e.get("name", "?"), 0) + 1
        lines.append("")
        lines.append("  instants: " + ", ".join(
            f"{n}×{c}" for n, c in sorted(per_i.items())))
    return "\n".join(lines)


def summarize(events: List[dict], last: int = 0) -> str:
    if last > 0:
        steps = sorted({e.get("step", 0) for e in events})[-last:]
        events = [e for e in events if e.get("step", 0) in set(steps)]
    by_name = _series(events)
    lines: List[str] = []
    n_steps = len({e.get("step", 0) for e in events})
    lines.append(f"telemetry report: {len(events)} events over "
                 f"{n_steps} steps")

    phase = {n: s for n, s in by_name.items()
             if n.startswith("Train/Step/") and n.endswith("_ms")}
    if phase:
        lines.append("")
        lines.append("step time (ms)")
        lines.append(f"  {'phase':<16} {'count':>6} {'mean':>10} "
                     f"{'min':>10} {'max':>10} {'last':>10}")
        for name, recs in phase.items():
            vals = [r["value"] for r in recs]
            label = name[len("Train/Step/"):-len("_ms")]
            lines.append(f"  {label:<16} {len(vals):>6} "
                         f"{sum(vals) / len(vals):>10.2f} {min(vals):>10.2f} "
                         f"{max(vals):>10.2f} {vals[-1]:>10.2f}")

    comm: Dict[str, Dict[str, float]] = {}
    for name, recs in by_name.items():
        if not name.startswith("Comm/"):
            continue
        _, op, kind = name.split("/", 2)
        # per-trace cumulative counters: the last sample is the total
        comm.setdefault(op, {})[kind] = recs[-1]["value"]
    if comm:
        lines.append("")
        lines.append("comm volume (per compiled step)")
        lines.append(f"  {'op':<24} {'count':>6} {'bytes':>14}")
        for op, kinds in sorted(comm.items()):
            lines.append(f"  {op:<24} {int(kinds.get('count', 0)):>6} "
                         f"{_fmt_bytes(kinds.get('bytes', 0.0)):>14}")

    mem = {n: s for n, s in by_name.items() if n.startswith("Memory/")}
    if mem:
        lines.append("")
        lines.append("device memory")
        for name, recs in sorted(mem.items()):
            vals = [r["value"] for r in recs]
            lines.append(f"  {name[len('Memory/'):]:<16} "
                         f"last {_fmt_bytes(vals[-1]):>14}   "
                         f"max {_fmt_bytes(max(vals)):>14}")

    other = {n: s for n, s in by_name.items()
             if n not in phase and n not in mem
             and not n.startswith("Comm/")}
    if other:
        lines.append("")
        lines.append("scalars (last value)")
        for name, recs in other.items():
            lines.append(f"  {name:<32} {recs[-1]['value']:.6g}")
    return "\n".join(lines)


def fleet(events: List[dict]) -> str:
    """``--fleet``: the fleet observability plane's offline view — the
    cross-replica ``Fleet/*`` rollup, the per-tenant SLO table
    (``Serving/tenant/*``), and the burn-rate alert history — rendered from
    one or more (merged, provenance-tagged) per-replica JSONL files."""
    by_name = _series(events)
    have = any(n.startswith(("Fleet/", "Serving/tenant/")) for n in by_name)
    if not have:
        return ("fleet: no Fleet/* or Serving/tenant/* events in this file\n"
                "  (enable the serving.obs block and publish via "
                "router.publish_fleet_obs_telemetry)")
    lines = ["fleet observability"]
    sources = sorted({e["source"] for e in events if "source" in e})
    if sources:
        lines.append(f"  merged from {len(sources)} file(s): "
                     + ", ".join(sources))

    # -- per-replica rollup (last sample per series wins) ---------------- #
    replicas: Dict[str, Dict[str, float]] = {}
    for name, recs in by_name.items():
        parts = name.split("/")
        if name.startswith("Fleet/replica") and len(parts) == 3:
            replicas.setdefault(parts[1][len("replica"):],
                                {})[parts[2]] = recs[-1]["value"]
    if replicas:
        cols = ("live", "queue_depth", "completed", "goodput_frac",
                "ttft_ms_p99", "e2e_ms_p99")
        lines.append("")
        lines.append("  per-replica rollup (last sample)")
        lines.append("  " + f"{'replica':<9}"
                     + "".join(f"{c:>14}" for c in cols))
        for r in sorted(replicas, key=lambda x: (len(x), x)):
            row = replicas[r]
            lines.append("  " + f"{r:<9}" + "".join(
                f"{row.get(c, 0.0):>14.3f}" for c in cols))
    agg = {n[len("Fleet/agg/"):]: recs[-1]["value"]
           for n, recs in by_name.items() if n.startswith("Fleet/agg/")}
    if agg:
        lines.append("")
        lines.append("  fleet aggregates (last sample)")
        for key in ("completed_sum", "tokens_emitted_sum",
                    "goodput_frac_mean", "goodput_frac_min",
                    "queue_wait_ms_p99_merged", "ttft_ms_p99_merged",
                    "itl_ms_p99_merged", "e2e_ms_p99_merged"):
            if key in agg:
                lines.append(f"    {key:<28} {agg[key]:,.3f}")
    outlier = {n[len("Fleet/outlier/"):]: recs[-1]["value"]
               for n, recs in by_name.items()
               if n.startswith("Fleet/outlier/")}
    if outlier:
        worst = max(outlier.items(), key=lambda kv: kv[1])
        lines.append(f"    worst replica-outlier delta: {worst[0]} "
                     f"+{worst[1] * 100:.1f}% over the median replica")

    # -- per-tenant SLO table -------------------------------------------- #
    tenants: Dict[str, Dict[str, float]] = {}
    for name, recs in by_name.items():
        parts = name.split("/")
        if name.startswith("Serving/tenant/") and len(parts) == 4:
            tenants.setdefault(parts[2], {})[parts[3]] = recs[-1]["value"]
    if tenants:
        lines.append("")
        lines.append("  per-tenant SLO accounting (last sample)")
        lines.append(f"  {'tenant':<16} {'completed':>10} {'rejected':>9} "
                     f"{'goodput':>9} {'ttft p99':>10} {'burn rate':>10} "
                     f"{'alerts':>7}")
        for t in sorted(tenants):
            row = tenants[t]
            lines.append(
                f"  {t:<16} {row.get('completed', 0.0):>10.0f} "
                f"{row.get('rejected', 0.0):>9.0f} "
                f"{row.get('goodput_frac', 0.0):>9.3f} "
                f"{row.get('ttft_p99_ms', 0.0):>8.1f}ms "
                f"{row.get('slo_burn_rate', 0.0):>10.2f} "
                f"{row.get('slo_burn_alerts', 0.0):>7.0f}")

    # -- burn-rate alert history ----------------------------------------- #
    # the alert counter is cumulative per tenant: every step where it rose
    # is one alert firing (multiwindow burn — fast AND slow window hot)
    fired: List[str] = []
    for name, recs in sorted(by_name.items()):
        parts = name.split("/")
        if not (name.startswith("Serving/tenant/")
                and name.endswith("/slo_burn_alerts")):
            continue
        prev = 0.0
        for r in recs:
            if r["value"] > prev:
                src = f" [{r['source']}]" if "source" in r else ""
                fired.append(f"    step {r.get('step', 0):>6}  "
                             f"tenant {parts[2]}  alert "
                             f"#{int(r['value'])}{src}")
            prev = max(prev, r["value"])
    lines.append("")
    if fired:
        lines.append(f"  burn-rate alert history ({len(fired)} firing(s))")
        lines.extend(fired)
    else:
        lines.append("  burn-rate alert history: none fired")
    return "\n".join(lines)


def tuning(events: List[dict]) -> str:
    """``--tuning``: the self-tuning runtime's offline view — fleet totals
    (trials/accepts/reverts/vetoes/retunes), the per-knob state table, and
    the accepted-winner history, rendered from ``Tune/*`` events emitted by
    ``deepspeed_tpu/tuning`` (docs/tuning.md)."""
    by_name = _series(events)
    if not any(n.startswith("Tune/") for n in by_name):
        return ("tuning: no Tune/* events in this file\n"
                "  (enable the `tuning` config block — training — or the "
                "serving router's `tuning` block)")
    lines = ["self-tuning runtime"]

    totals = {n[len("Tune/total/"):]: recs[-1]["value"]
              for n, recs in by_name.items() if n.startswith("Tune/total/")}
    if totals:
        lines.append("  totals: " + "  ".join(
            f"{k}={int(totals[k])}"
            for k in ("trials", "accepts", "reverts", "vetoes", "retunes",
                      "open_knobs", "closed_knobs") if k in totals))

    # -- per-knob state table (last sample per metric wins) -------------- #
    knobs: Dict[str, Dict[str, float]] = {}
    for name, recs in by_name.items():
        parts = name.split("/")
        if name.startswith("Tune/knob/") and len(parts) == 4:
            knobs.setdefault(parts[2], {})[parts[3]] = recs[-1]["value"]
    if knobs:
        lines.append("")
        lines.append("  per-knob state (value = choice index; Δ = score "
                     "best-vs-baseline, sign per the knob's objective)")
        lines.append(f"  {'knob':<28} {'state':>7} {'value':>6} "
                     f"{'trials':>7} {'accepts':>8} {'reverts':>8} "
                     f"{'vetoes':>7} {'retunes':>8} {'Δ score':>10}")
        for k in sorted(knobs):
            row = knobs[k]
            state = "open" if row.get("active", 0.0) else "closed"
            delta = row.get("score_delta")
            lines.append(
                f"  {k:<28} {state:>7} {row.get('value', 0.0):>6.0f} "
                f"{row.get('trials', 0.0):>7.0f} "
                f"{row.get('accepts', 0.0):>8.0f} "
                f"{row.get('reverts', 0.0):>8.0f} "
                f"{row.get('vetoes', 0.0):>7.0f} "
                f"{row.get('retunes', 0.0):>8.0f} "
                + (f"{delta:>10.4f}" if delta is not None else f"{'-':>10}"))

    # -- accepted-winner history ----------------------------------------- #
    # per-knob accept counters are cumulative: each rise is one accepted arm
    accepted: List[str] = []
    for name, recs in sorted(by_name.items()):
        parts = name.split("/")
        if not (name.startswith("Tune/knob/")
                and name.endswith("/accepts") and len(parts) == 4):
            continue
        prev = 0.0
        for r in recs:
            if r["value"] > prev:
                src = f" [{r['source']}]" if "source" in r else ""
                accepted.append(f"    step {r.get('step', 0):>6}  "
                                f"{parts[2]}  accept "
                                f"#{int(r['value'])}{src}")
            prev = max(prev, r["value"])
    lines.append("")
    if accepted:
        lines.append(f"  accepted winners ({len(accepted)})")
        lines.extend(accepted)
    else:
        lines.append("  accepted winners: none yet")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", nargs="*",
                    help="path(s) to events.jsonl telemetry file(s) — "
                         "multiple files (a fleet's per-replica monitors) "
                         "are merged with provenance tags (optional with "
                         "--trace)")
    ap.add_argument("--last", type=int, default=0,
                    help="restrict to the last N steps")
    ap.add_argument("--comm-efficiency", action="store_true",
                    help="print collective count / total algorithmic bytes / "
                         "bytes-per-step (comm-volume regression check)")
    ap.add_argument("--reliability", action="store_true",
                    help="summarize Reliability/* events: skipped steps, "
                         "watchdog trips, checkpoint save/restore/rollback "
                         "counts")
    ap.add_argument("--serving", action="store_true",
                    help="summarize Serving/prefix_cache/* counters "
                         "(hit-rate, prefill tokens saved, retained-pool "
                         "occupancy, evictions), Serving/spec/* "
                         "speculative-decoding counters (accept rate, mean "
                         "accepted length, tokens per model step, verify "
                         "batch occupancy), Serving/sched/* scheduler "
                         "counters (queue depth, admitted/rejected/"
                         "preempted, queue-wait percentiles, goodput-under-"
                         "SLO), Serving/router/* placement counters, and "
                         "Serving/fleet/* resilience counters (failovers, "
                         "circuit-breaker transitions, shed requests, "
                         "degradation level)")
    ap.add_argument("--latency", action="store_true",
                    help="summarize Serving/latency/* SLO percentiles: "
                         "TTFT / inter-token / queue / e2e p50-p90-p99")
    ap.add_argument("--memory", action="store_true",
                    help="summarize the tiered memory subsystem's "
                         "Memory/tier/* stream (per-tier resident bytes, "
                         "transfer volume, measured compute-overlap "
                         "fraction, prefetch hit/miss, KV host-spill pool) "
                         "plus the Memory/* allocator gauges")
    ap.add_argument("--compile", action="store_true", dest="compile_",
                    help="summarize Compile/* recompilation-sentinel "
                         "counters (compiles, cache hits, recompiles, "
                         "compile wall time) and the per-program MFU "
                         "attribution from Train/mfu/* + Serving/mfu/*")
    ap.add_argument("--anomalies", action="store_true",
                    help="summarize recorded Anomaly/* findings (spikes, "
                         "drift, stragglers) and replay the rolling-median/"
                         "MAD detector offline over the Train/Step/*_ms "
                         "series")
    ap.add_argument("--fleet", action="store_true",
                    help="summarize the fleet observability plane: "
                         "cross-replica Fleet/* rollups (per-replica rows, "
                         "aggregates, outlier deltas), the per-tenant SLO "
                         "table (Serving/tenant/* goodput, TTFT p99, burn "
                         "rate), and the burn-rate alert history — pass "
                         "several per-replica events.jsonl paths to merge "
                         "them with provenance tags")
    ap.add_argument("--tuning", action="store_true",
                    help="summarize the self-tuning runtime: Tune/total/* "
                         "fleet counters, the per-knob state table "
                         "(trials/accepts/reverts/vetoes/retunes, applied "
                         "choice, score delta), and the accepted-winner "
                         "history")
    ap.add_argument("--trace", metavar="TRACE_JSON",
                    help="summarize a Chrome-trace/Perfetto JSON flight-"
                         "recorder dump (span durations, slowest spans)")
    ap.add_argument("--all", action="store_true",
                    help="run every section (summary, comm efficiency, "
                         "reliability, serving, latency, compile, "
                         "anomalies, fleet, tuning) in one pass")
    args = ap.parse_args(argv)
    if args.trace:
        try:
            print(trace_report(args.trace))
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        if not args.path:
            return 0
        print()
    if not args.path:
        ap.error("path to an events.jsonl file is required "
                 "(or use --trace <out.json>)")
    try:
        events = load_events(*args.path)
    except OSError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    if not events:
        print(f"error: no telemetry events in {', '.join(args.path)}",
              file=sys.stderr)
        return 1
    if args.all:
        sections = [summarize(events, last=args.last), comm_efficiency(events),
                    reliability(events), serving(events), latency(events),
                    memory_report(events), compile_report(events),
                    anomalies(events), fleet(events), tuning(events)]
        print("\n\n".join(sections))
        return 0
    if args.compile_:
        print(compile_report(events))
        return 0
    if args.anomalies:
        print(anomalies(events))
        return 0
    if args.comm_efficiency:
        print(comm_efficiency(events))
        return 0
    if args.reliability:
        print(reliability(events))
        return 0
    if args.serving:
        print(serving(events))
        return 0
    if args.latency:
        print(latency(events))
        return 0
    if args.memory:
        print(memory_report(events))
        return 0
    if args.fleet:
        print(fleet(events))
        return 0
    if args.tuning:
        print(tuning(events))
        return 0
    print(summarize(events, last=args.last))
    return 0


if __name__ == "__main__":
    sys.exit(main())
