"""Operations and bytes of a ReBranch decoder-only LM, from its sizes.

Every projection is an int8 ROM trunk (d_in x d_out) beside a float
branch: compress d_in -> d_in/D, core -> d_out/U, decompress -> d_out.
Keys follow the model's published ``config.json`` (``hidden_size``,
``intermediate_size``, ...).  A multiply-accumulate is 2 operations.
"""

from __future__ import annotations


def _dims(body: dict) -> dict:
    d = body["hidden_size"]
    h, kv = body["num_attention_heads"], body["num_key_value_heads"]
    dh = body.get("head_dim") or d // h
    return {"d": d, "h": h, "kv": kv, "dh": dh,
            "ff": body["intermediate_size"], "L": body["num_hidden_layers"],
            "V": body["vocab_size"], "D": body["rebranch"]["d_ratio"],
            "U": body["rebranch"]["u_ratio"]}


def projections(body: dict) -> list[tuple[int, int]]:
    """(d_in, d_out) of the ROM projections of one layer: q, k, v, o,
    gate, up, down."""
    m = _dims(body)
    d, qd, kvd, ff = m["d"], m["h"] * m["dh"], m["kv"] * m["dh"], m["ff"]
    return [(d, qd), (d, kvd), (d, kvd), (qd, d), (d, ff), (d, ff), (ff, d)]


def token_work(body: dict, context: float) -> dict:
    """One token through every layer and the tied readout, attending to
    ``context`` positions: int8 trunk operations and float operations
    (branches, attention scores and values, readout)."""
    m = _dims(body)
    trunk = branch = 0
    for d_in, d_out in projections(body):
        c, u = max(1, d_in // m["D"]), max(1, d_out // m["U"])
        trunk += 2 * d_in * d_out
        branch += 2 * (d_in * c + c * u + u * d_out)
    attn = 4 * m["h"] * m["dh"] * context
    return {"int8_ops": m["L"] * trunk,
            "float_ops": m["L"] * (branch + attn) + 2 * m["d"] * m["V"]}


def param_bytes(body: dict) -> int:
    """Bytes of the served parameters: int8 trunks and table, f32
    scales, compress/decompress, cores, biases and norms."""
    m = _dims(body)
    per_layer = 0
    for d_in, d_out in projections(body):
        c, u = max(1, d_in // m["D"]), max(1, d_out // m["U"])
        per_layer += d_in * d_out + 4 * (d_out + d_in * c + c * u + u * d_out)
    qd, kvd = m["h"] * m["dh"], m["kv"] * m["dh"]
    per_layer += 4 * (qd + 2 * kvd) + 4 * 2 * m["d"]      # biases, norms
    return m["L"] * per_layer + m["V"] * m["d"] + 4 * m["V"] + 4 * m["d"]


def kv_bytes_per_position(body: dict, itemsize: int) -> int:
    """Bytes of keys and values one cached position holds, all layers."""
    m = _dims(body)
    return 2 * m["L"] * m["kv"] * m["dh"] * itemsize


def decode_step_work(body: dict, live: list[int], itemsize: int) -> dict:
    """One batched decode step: the rows in ``live`` (each row's cached
    length) each make one token.  Bytes: every parameter once, the live
    keys and values of those rows read, one new position written."""
    ops = {"int8_ops": 0, "float_ops": 0}
    for n in live:
        w = token_work(body, n + 1)
        ops["int8_ops"] += w["int8_ops"]
        ops["float_ops"] += w["float_ops"]
    per = kv_bytes_per_position(body, itemsize)
    ops["bytes"] = param_bytes(body) + per * sum(n + 1 for n in live)
    return ops
