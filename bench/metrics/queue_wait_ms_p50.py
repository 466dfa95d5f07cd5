"""Median time a request spent queued (ms): ``request.queue`` spans,
from ``Request.submit_s`` to the dispatch of its first prefill call,
over every request admitted in the window.  The program's own stamps,
where ``admit_wait_ms_p50`` infers admission around the benchmark's
``step()`` calls.  Moves ``ttft_p95_ms``."""

import statistics


def read(view):
    spans = getattr(view["trace"], "spans", None)
    waits = [s["end"] - s["start"] for s in spans or ()
             if s["name"] == "request.queue"]
    return 1e-6 * statistics.median(waits) if waits else None
