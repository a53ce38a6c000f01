"""Host spans the benchmark records around its calls into the program.

Each span is written twice: into the benchmark's own list (host clock, for
the per-tick arithmetic) and, through ``jax.profiler.TraceAnnotation``, into
the profiler's trace, where the reduction attributes the device's idle gaps
to what the host was doing. Spans inside the program are a later PR.
"""

from __future__ import annotations

import contextlib
import time
from typing import List, Tuple

import jax

PREFIX = "bench:"   # how the reduction finds the benchmark's spans in a trace


class Spans:
    def __init__(self) -> None:
        self.records: List[Tuple[str, float, float]] = []  # name, start, end

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))
