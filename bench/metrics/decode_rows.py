"""Mean rows the batcher decoded per decode step in the window (a count).

Read around the benchmark's own ``step()`` calls: a row counts when the
step gave it a token by decoding (not a first token from a prefill).
Moves ``tokens_per_s``."""


def read(view):
    rows = view["step_rows"]
    return sum(rows) / len(rows) if rows else None
