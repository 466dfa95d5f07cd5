"""The fused ReBranch conv kernel's share of its roofline (%).

Device time: every event of the Pallas kernel ``rebranch_conv`` inside
a traced forward.  Least time: the work of the 20 sites' kernels for
those forwards (int8 trunk operations at the int8 peak plus the float
compress at the bf16 peak, against the kernels' input, weight and
output bytes at the HBM bandwidth; ``counts.conv``).  Moves
``images_per_s``."""

from bench import peaks as peaks_lib

KERNEL = "rebranch_conv"


def read(view):
    trace = view["trace"]
    if trace is None or not trace.device:
        return None
    forwards = trace.module_events("forward")
    kernels = [e for e in trace.kernel_events(KERNEL)
               if any(f.start <= e.start and e.end <= f.end
                      for f in forwards)]
    if not forwards or not kernels:
        return None
    work = view["forward"]
    least, _ = peaks_lib.least_time(
        view["peaks"](), int8_ops=work["int8_ops"],
        float_ops=work["kernel_float_ops"], bytes_moved=work["kernel_bytes"])
    return 100.0 * least * len(forwards) / sum(e.seconds for e in kernels)
