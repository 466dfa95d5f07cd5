"""Median time to first token over the window's requests (ms).

Timed as ``ttft_p95_ms`` is, from each request's due time, on the host
clock around the benchmark's own ``step()`` calls.  With a few dozen
requests in a window the p95 is the second or third slowest; the median
is the steadier reading of the same queue.  Moves ``ttft_p95_ms``."""

import statistics


def read(view):
    ttft = view["ttft_s"]
    return 1e3 * statistics.median(ttft) if ttft else None
