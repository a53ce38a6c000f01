"""What the program's own compile account (``deepspeed_tpu/telemetry/
compile.py CompileAccount``: JAX's monitoring events of the whole process)
holds of this run's SET-UP: its totals over the events that ended before the
window opened. The opening is read on the account's clock
(``time.perf_counter``) from the run's own series - the completion time of
the tick or the step at ``window[0]`` - so nothing the window compiled,
traced or analysed counts. ``what`` is a key of ``CompileAccount.totals``.
Reports nothing where the program has no account (an older commit) or the
account is empty (a configuration that leaves the compile monitor off)."""

COMPLETIONS = {"closed_loop": "tick_completion_s", "train": "step_completion_s"}


def read(ctx, what):
    try:
        from deepspeed_tpu.telemetry.compile import process_account
    except ImportError:
        return None
    account = process_account()
    if not account.events_seen:
        return None
    series = ctx["series"]
    t_open = series[COMPLETIONS[series["kind"]]][ctx["window"][0]]
    return account.totals(before=t_open)[what]
