"""Config-gated JAX profiler trace sessions + phase annotations.

``ProfilerSession`` brackets a window of global steps with
``jax.profiler.start_trace`` / ``stop_trace`` (the xprof/tensorboard trace the
T3-style overlap analysis needs), driven by the ``profiler`` config block:
``{"enabled", "start_step", "end_step", "output_dir"}``. While a session
runs, every ``Tracer.span`` of the program is on its timeline as
``dstpu:<name>`` (telemetry/trace.py).
"""

from __future__ import annotations

import os
import tempfile
from typing import Optional

import jax

from ..utils.logging import log_dist, logger


class ProfilerSession:
    """One trace window per run: starts when the step counter enters
    ``[start_step, end_step]``, stops when it leaves. Rank-0 only (one trace
    per job, matching the monitor gating). A profiler failure must never take
    down training — errors disable the session and are kept on ``.error``."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.active = False
        self.done = False
        self.error: Optional[str] = None
        self.output_dir: Optional[str] = None

    @property
    def enabled(self) -> bool:
        return bool(getattr(self.cfg, "enabled", False)) and \
            jax.process_index() == 0

    def maybe_start(self, step: int) -> None:
        """Call with the global step about to execute."""
        if not self.enabled or self.done or self.active:
            return
        if step < int(getattr(self.cfg, "start_step", 1)):
            return
        out = getattr(self.cfg, "output_dir", "") or \
            os.path.join(tempfile.gettempdir(), "dstpu_profile")
        try:
            os.makedirs(out, exist_ok=True)
            jax.profiler.start_trace(out)
            self.active = True
            self.output_dir = out
            log_dist(f"profiler: trace started at step {step} → {out}")
        except Exception as e:
            self.error = str(e)
            self.done = True
            logger.warning(f"profiler session disabled: {e}")

    def maybe_stop(self, step: int) -> None:
        """Call with the global step that just completed."""
        if not self.active or step < int(getattr(self.cfg, "end_step", 1)):
            return
        try:
            jax.profiler.stop_trace()
            log_dist(f"profiler: trace stopped after step {step} "
                     f"({self.output_dir})")
        except Exception as e:
            self.error = str(e)
            logger.warning(f"profiler stop_trace failed: {e}")
        self.active = False
        self.done = True

    def close(self) -> None:
        """Shutdown path: never leave a trace session open."""
        if self.active:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self.active = False
            self.done = True
