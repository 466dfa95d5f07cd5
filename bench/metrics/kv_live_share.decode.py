"""Mean, over the window's decode steps, of the KV positions that hold
live entries over the positions of the pool's blocks (%): the pool's
host-side counts on each ``batcher.decode`` span.  Moves
``tokens_per_s``."""


def read(view):
    spans = getattr(view["trace"], "spans", None)
    steps = [s["attrs"] for s in spans or ()
             if s["name"] == "batcher.decode"
             and s["attrs"].get("kv_positions")]
    if not steps:
        return None
    return 100.0 * sum(a["kv_live"] / a["kv_positions"]
                       for a in steps) / len(steps)
