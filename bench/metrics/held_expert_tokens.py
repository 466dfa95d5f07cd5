"""Mean token assignments per held expert per MoE layer per decode step.

Read from the program's on-device counter (the cache's
``counters.held_tokens``: each held expert's assignments from live rows
in decode steps), once as the window opens and once as it closes; the
difference over the decode steps between.  With R rows, k experts per
token and E routed experts, about R * k / E.  Moves ``tokens_per_s``."""


def read(view):
    held = view.get("held")
    if not held or not held["steps"]:
        return None
    counts = held["counts"]
    return float(counts.sum()) / (held["steps"] * counts.size)
