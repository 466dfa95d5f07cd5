"""Share of the traced window in which the chip is idle while the host
is inside ``batcher.admit`` (%): prefill dispatch, the pool's adoption
scatters and the first token's fetch.  At most ``device_idle.chat``.
Moves ``ttft_p95_ms``."""


def read(view):
    trace = view["trace"]
    if not getattr(trace, "spans", None):
        return None
    return trace.idle_while("batcher.admit")
