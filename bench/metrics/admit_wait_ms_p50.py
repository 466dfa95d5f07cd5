"""Median wait from a request's due time to its first prefill chunk (ms).

Read around the benchmark's own ``step()`` calls: a request counts as
admitted in the step after which the batcher's queue no longer holds
it, and its wait runs to that step's start.  Moves ``ttft_p95_ms``."""

import statistics


def read(view):
    waits = view["admit_wait_s"]
    return 1e3 * statistics.median(waits) if waits else None
