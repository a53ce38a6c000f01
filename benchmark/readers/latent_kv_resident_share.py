"""Of the latent pool's blocks, the share sequences hold, in percent: the
mean over the traced window's ``sched_tick`` spans of ``latent_blocks_live``
over ``latent_blocks`` (what the scheduler puts on its tick for a family
with a latent cache). A program whose ticks carry neither reports
nothing."""

import statistics

from benchmark.harness import program_spans as ps


def read(ctx):
    program = ps.load(ctx)
    if program is None:
        return None
    shares = [s.arg("latent_blocks_live") / s.arg("latent_blocks")
              for s in ps.named(program.spans, "sched_tick",
                                ctx["trace"].window())
              if s.arg("latent_blocks")]
    return 100.0 * statistics.mean(shares) if shares else None
