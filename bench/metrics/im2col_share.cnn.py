"""Share of the ``forward`` program's device self time in ops scoped
``patches`` (%): the im2col patch matrix each conv site builds in HBM
before its ``rebranch_conv`` kernel.  Moves ``images_per_s``."""


def read(view):
    trace = view["trace"]
    if not getattr(trace, "scopes", None):
        return None
    return trace.scope_share("forward", "patches")
