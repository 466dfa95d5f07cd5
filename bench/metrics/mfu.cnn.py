"""A whole forward's share of the chip's peak (%).

The least time of one forward's operations (int8 trunk operations at
the int8 peak, float branch and predictor operations at the bf16 peak;
``counts.conv``) over the mean device time of the traced forwards
(``jit_forward`` program events).  Moves ``images_per_s``."""

from bench import peaks as peaks_lib


def read(view):
    trace = view["trace"]
    if trace is None or not trace.device:
        return None
    forwards = trace.module_events("forward")
    if not forwards:
        return None
    work = view["forward"]
    least, _ = peaks_lib.least_time(view["peaks"](),
                                    int8_ops=work["int8_ops"],
                                    float_ops=work["float_ops"])
    mean = sum(e.seconds for e in forwards) / len(forwards)
    return 100.0 * least / mean
