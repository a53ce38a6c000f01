"""What the traffic kinds share: the run's context, the traced window and
the series file."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
from typing import Any, Dict, List, Optional

from ..harness.manifest import Cell
from ..harness.spans import Spans


@dataclasses.dataclass
class Run:
    """One invocation of the benchmark, as the traffic kind sees it."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    out_dir: str
    t_process: float                 # perf_counter() when run.py started
    device: Dict[str, Any]
    spans: Spans = dataclasses.field(default_factory=Spans)

    def say(self, **fields) -> None:
        """An earlier line of the output: one JSON object, never the last."""
        print(json.dumps(fields), flush=True)

    @contextlib.contextmanager
    def traced_window(self):
        """Profile what runs inside, under the ``window`` span the trace
        reduction measures against. Python-level tracing is off: it slows
        the host it is meant to observe."""
        import jax

        trace_dir = os.path.join(self.out_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with self.spans.span("window"):
                yield trace_dir
        finally:
            jax.profiler.stop_trace()

    def write_series(self, series: Dict[str, Any]) -> str:
        path = os.path.join(self.out_dir, "series.json")
        with open(path, "w") as f:
            json.dump(series, f)
        return path


@dataclasses.dataclass
class Record:
    """What a traffic kind hands back to ``run.py``."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]          # by metric name, as measured
    context: Dict[str, Any]               # what the per-layer readers read
    trace_dir: Optional[str] = None
    why_not_correct: List[str] = dataclasses.field(default_factory=list)
