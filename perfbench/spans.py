"""Host spans of the harness's own calls into the program, kept in memory
as (query, name, start, end) in seconds of the host's wall clock.  The
profiler's trace counts its nanoseconds from a wall-clock instant that it
records (``profile_start_time``), so the spans are laid beside the device's
operations by that clock (``trace_reduce.add_host_spans``); the profiler's
own host tracer stays off, because on the TPU's host it writes millions of
runtime events into every trace.  In a traced run ``sync`` waits for the
device, so that a span ends when its work has."""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, traced: bool):
        self.traced = traced
        self.rows = []          # (query index, name, t0, t1)
        self.query = -1

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.time()
        try:
            yield
        finally:
            self.rows.append((self.query, name, t0, time.time()))

    def sync(self, tree):
        """In a traced run, wait until ``tree`` is on the device."""
        if self.traced:
            import jax
            jax.block_until_ready(tree)
        return tree

    def seconds(self, name):
        return [t1 - t0 for _q, n, t0, t1 in self.rows if n == name]
