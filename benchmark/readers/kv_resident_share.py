"""Of the KV tokens ONE block table would keep resident for the sequences the
traced window's calls attend - every layer the whole context - the share the
two kinds of KV state keep (``costs_window.resident_share`` over the calls'
``kv_tokens_full`` / ``kv_tokens_window``, decode rows and chunks alike), in
percent: lower is better, and 100 is a cache of one kind. Counted in tokens
the walks read; what rounds a window up to whole blocks is left out. A
program whose spans carry no kinds reports nothing."""

from benchmark.harness import costs_window
from benchmark.readers import window_calls


def read(ctx):
    calls = window_calls.calls(ctx)
    full = sum(c["full"] for c in calls)
    if not full:
        return None
    return 100.0 * costs_window.resident_share(
        ctx["cell"].model, full, sum(c["window"] for c in calls))
