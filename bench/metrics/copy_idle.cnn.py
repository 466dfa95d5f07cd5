"""Share of the traced window in which the chip is idle while the host
is inside ``cnn.copy_in`` or ``cnn.copy_out`` (%): the frames' copy to
the device (with the host's layout transpose) and the detector output's
copy back.  At most ``device_idle.cnn``.  Moves ``images_per_s``."""


def read(view):
    trace = view["trace"]
    if not getattr(trace, "spans", None):
        return None
    return trace.idle_while("cnn.copy_in", "cnn.copy_out")
