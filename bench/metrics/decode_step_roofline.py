"""The jitted ``decode_step`` program's share of its roofline (%).

Device time: the ``jit_decode_step`` program events in the trace.  Least
time per step: every parameter read once plus the live keys and values
of the rows that decoded (bytes at the HBM bandwidth), against their
operations (int8 trunk at the int8 peak, float at the bf16 peak),
averaged over the steps the benchmark made while tracing
(``counts.lm``).  Moves ``tokens_per_s``."""

from bench import peaks as peaks_lib


def read(view):
    trace = view["trace"]
    if trace is None or not trace.device:
        return None
    events = trace.module_events("decode_step")
    steps = [s for s in view["traced_steps"] if s]
    if not events or not steps:
        return None
    counts, p = view["lm_counts"], view["peaks"]()
    least = 0.0
    for live in steps:
        w = counts.decode_step_work(view["body"], live, view["kv_itemsize"])
        least += peaks_lib.least_time(p, int8_ops=w["int8_ops"],
                                      float_ops=w["float_ops"],
                                      bytes_moved=w["bytes"])[0]
    least /= len(steps)
    return 100.0 * least * len(events) / sum(e.seconds for e in events)
