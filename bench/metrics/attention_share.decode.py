"""Share of the ``decode_step`` program's device self time in ops
scoped ``attention`` but not ``branch`` (%): the paged KV write and
gather, the scores and softmax over the horizon, and the q/k/v/o
trunks.  The projections' branches count in ``branch_share.decode``,
so the two shares add up to at most 100.  Moves ``tokens_per_s``."""


def read(view):
    trace = view["trace"]
    if not getattr(trace, "scopes", None):
        return None
    return trace.scope_share("decode_step", "attention", without="branch")
