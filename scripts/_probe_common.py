"""Shared finalizer for the probe scripts (ONE failure-detection rule): every
probe computes ``detail.ok`` itself via this rule, prints its one JSON line,
and exits non-zero when the flag is false.
"""

import json


# Structured failure markers (ADVICE r5): a failure row must START with one
# of these prefixes ("error: <detail>"), or be a dict with status == "error".
# The old substring scan flagged benign labels ("failover", "timeout_budget")
# and silently poisoned ok — prefix matching keeps producers explicit.
_BAD_PREFIXES = ("error:", "fail:", "failed:", "timeout:")
# dedicated failure slots: any non-empty string under these keys is a failure
# even without the prefix (every probe stores its traceback tail there)
_BAD_KEYS = ("error", "exception")


def _bad(v, key=None) -> bool:
    if isinstance(v, str):
        if key in _BAD_KEYS:
            return bool(v.strip())
        return v.lower().lstrip().startswith(_BAD_PREFIXES)
    if isinstance(v, dict):
        if str(v.get("status", "")).strip().lower() == "error":
            return True
        return any(_bad(x, key=k) for k, x in v.items())
    if isinstance(v, (list, tuple)):
        return any(_bad(x) for x in v)
    return False


def finalize(result: dict, ok=None) -> int:
    """Set ``detail.ok``, print the one stdout JSON line, and return the
    process exit code (0 only when ok).

    ``ok=None`` (the default rule): False if any nested detail value carries
    a STRUCTURED failure marker — a string starting with ``error:`` /
    ``fail:`` / ``failed:`` / ``timeout:``, a dict with ``status: "error"``,
    or any non-empty string under an ``error``/``exception`` key. Benign
    labels that merely contain those words ('failover', 'skipped: <budget>')
    are not failures. An explicit bool overrides the scan for probes where a
    failure row is part of a successful run (longctx records its OOM
    frontier by design)."""
    result["detail"]["ok"] = (not _bad(result["detail"])) if ok is None \
        else bool(ok)
    print(json.dumps(result), flush=True)
    return 0 if result["detail"]["ok"] else 1
