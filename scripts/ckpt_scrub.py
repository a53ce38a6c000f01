#!/usr/bin/env python
"""Offline checkpoint-integrity scrub (docs/reliability.md "Numerics
integrity & SDC").

Walks every checkpoint tag under one or more save dirs and re-verifies the
durable-save manifest (per-file SHA-256 + byte size, written at seal time by
``runtime/checkpoint/manifest.py``) — the at-rest half of the SDC story: the
in-flight fingerprint plane catches corruption between replicas, this tool
catches bit rot / torn copies / tampering AFTER the bytes hit disk, e.g. on
a cron or before promoting a checkpoint across clusters.

Per tag it prints one verdict row::

    verified  universal_step3   step 3     universal  12 files verified
    corrupt   universal_step6   step 6     universal  sha256 mismatch for ...

and exits nonzero iff anything is ``corrupt`` (or the ``latest`` pointer
dangles). ``legacy`` tags (pre-manifest; loadable but unverifiable) and
leftover staging dirs are reported but never fatal.

Usage: python scripts/ckpt_scrub.py CKPT_DIR [CKPT_DIR ...] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.runtime.checkpoint.manifest import (  # noqa: E402
    MANIFEST_NAME, is_staging_name, tag_candidates, verify_manifest)


def _tag_step(tag_dir: str) -> int:
    try:
        with open(os.path.join(tag_dir, "meta.json")) as f:
            return int(json.load(f).get("global_steps", -1))
    except (OSError, ValueError, TypeError):
        return -1


def _is_universal(tag_dir: str) -> bool:
    try:
        from deepspeed_tpu.runtime.checkpoint.universal import is_universal_tag
        return bool(is_universal_tag(tag_dir))
    except Exception:
        return False


def scrub_dir(ckpt_dir: str) -> dict:
    """Verify every tag under ``ckpt_dir`` → a report dict (pure function of
    the directory; no engine, no jax arrays — safe on a cold host)."""
    report = {"dir": ckpt_dir, "tags": [], "staging": [], "latest": None,
              "latest_ok": True, "n_corrupt": 0, "n_legacy": 0,
              "n_verified": 0}
    if not os.path.isdir(ckpt_dir):
        report["latest_ok"] = False
        report["error"] = "not a directory"
        return report
    tags = tag_candidates(ckpt_dir)
    for name in tags:
        full = os.path.join(ckpt_dir, name)
        status, detail = verify_manifest(full)
        n_files = 0
        try:
            with open(os.path.join(full, MANIFEST_NAME)) as f:
                n_files = len(json.load(f).get("files", {}))
        except (OSError, ValueError, TypeError):
            pass
        report["tags"].append({
            "tag": name, "status": status, "detail": detail,
            "step": _tag_step(full), "universal": _is_universal(full),
            "files": n_files})
        report[f"n_{status}"] = report.get(f"n_{status}", 0) + 1
    # leftover staging/displaced dirs: harmless (never load candidates) but
    # worth surfacing — they mean a crash mid-save or mid-publish
    try:
        for name in sorted(os.listdir(ckpt_dir)):
            if is_staging_name(name) and \
                    os.path.isdir(os.path.join(ckpt_dir, name)):
                report["staging"].append(name)
    except OSError:
        pass
    # the latest pointer must name an existing, non-corrupt tag
    try:
        with open(os.path.join(ckpt_dir, "latest")) as f:
            latest = f.read().strip()
        report["latest"] = latest
        row = next((t for t in report["tags"] if t["tag"] == latest), None)
        report["latest_ok"] = bool(row and row["status"] != "corrupt")
    except OSError:
        pass  # no pointer is fine (hint-only dirs)
    return report


def _print_report(rep: dict) -> None:
    print(f"scrub {rep['dir']}: {len(rep['tags'])} tag(s), "
          f"{rep['n_verified']} verified, {rep['n_legacy']} legacy, "
          f"{rep['n_corrupt']} corrupt")
    for t in rep["tags"]:
        kind = "universal" if t["universal"] else "engine   "
        print(f"  {t['status']:<9} {t['tag']:<24} step {t['step']:<6} "
              f"{kind} {t['detail']}")
    for name in rep["staging"]:
        print(f"  staging   {name:<24} leftover staging dir (crash "
              f"mid-save; never a load candidate)")
    if rep["latest"] is not None and not rep["latest_ok"]:
        print(f"  DANGLING  latest -> {rep['latest']} (missing or corrupt)")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python scripts/ckpt_scrub.py",
        description="re-verify checkpoint manifests at rest")
    p.add_argument("dirs", nargs="+", help="checkpoint save dir(s) to scrub")
    p.add_argument("--json", action="store_true",
                   help="emit the full per-dir reports as one JSON object")
    args = p.parse_args(argv)
    reports = [scrub_dir(d) for d in args.dirs]
    bad = any(r["n_corrupt"] or not r["latest_ok"] or "error" in r
              for r in reports)
    if args.json:
        print(json.dumps({"ok": not bad, "reports": reports}, indent=2))
    else:
        for r in reports:
            _print_report(r)
        print(f"scrub verdict: {'FAIL' if bad else 'ok'}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
