"""Share of the traced window in which no operation ran on the chip (%).

1 - (union of the device's operation intervals) / (traced window),
averaged over the chips.  Moves ``ttft_p95_ms``."""


def read(view):
    trace = view["trace"]
    if trace is None or not trace.device or not trace.window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
