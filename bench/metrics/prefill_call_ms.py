"""Mean device time of one jitted ``prefill`` call (ms).

The ``jit_prefill`` program events in the trace: each is one chunk (or
one whole short prompt) of one request.  Moves ``ttft_p95_ms``."""


def read(view):
    trace = view["trace"]
    if trace is None or not trace.device:
        return None
    events = trace.module_events("prefill")
    if not events:
        return None
    return 1e3 * sum(e.seconds for e in events) / len(events)
