"""Harness spans: host intervals around each call into a layer.

Each span is kept in memory as (name, start_ns, end_ns) on the
``time.perf_counter_ns`` clock and, while the profiler runs, is also
written into its trace with ``jax.profiler.TraceAnnotation`` so that the
device's idle gaps can be attributed to what the host was doing.
"""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list[tuple[str, int, int]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            if self.annotate:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def durations_s(self, name: str) -> list[float]:
        return [(b - a) * 1e-9 for n, a, b in self.records if n == name]
