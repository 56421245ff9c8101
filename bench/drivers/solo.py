"""One campaign at a time: a labeling service that runs each customer's
pool to its commit before it starts the next.

The window starts campaigns back to back while ``seconds`` have not
passed; the campaign in flight then finishes, and the window ends at its
commit.  ``traced``, when given, is a context-manager factory that wraps
the window's first campaign (the profiler of a ``--trace 1`` run).
"""
from __future__ import annotations

import contextlib
import time


def run_window(env, seeds, seconds: float, traced=None):
    runs = []
    t_end = time.perf_counter() + seconds
    while True:
        ctx = traced() if traced is not None and not runs \
            else contextlib.nullcontext()
        with ctx:
            runs.append(env.run_campaign(next(seeds)))
        if time.perf_counter() >= t_end:
            return runs
