"""Share of the ``decode_step`` program's device self time in ops
scoped ``branch`` (%): the SRAM ReBranch of every projection, its
float32 ``C``/``U`` converts included.

Scope paths come from the compiled program's HLO metadata
(``bench/spantrace.py``); nothing to read in a trace without them.
Disjoint from ``attention_share.decode``.  Moves ``tokens_per_s``."""


def read(view):
    trace = view["trace"]
    if not getattr(trace, "scopes", None):
        return None
    return trace.scope_share("decode_step", "branch")
