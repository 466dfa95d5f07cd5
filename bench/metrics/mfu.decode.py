"""The decode window's share of the chip's peak (%).

Tokens decoded while tracing, times the least time of one token's
operations at the mean live context (int8 trunk at the int8 peak,
float at the bf16 peak; ``counts.lm``), over the traced window.
Moves ``tokens_per_s``."""

from bench import peaks as peaks_lib


def read(view):
    trace = view["trace"]
    steps = [s for s in view["traced_steps"] if s]
    if trace is None or not trace.device or not steps \
            or not view["traced_tokens"]:
        return None
    ctx = sum(sum(s) / len(s) for s in steps) / len(steps)
    w = view["lm_counts"].token_work(view["body"], ctx + 1)
    least, _ = peaks_lib.least_time(view["peaks"](), int8_ops=w["int8_ops"],
                                    float_ops=w["float_ops"])
    return 100.0 * view["traced_tokens"] * least / trace.window_s
