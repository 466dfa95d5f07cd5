"""Operations and bytes of a ReBranch DeepSeek-V2 decoder, one chip's share.

Keys follow the model's published ``config.json`` (``hidden_size``,
``kv_lora_rank``, ``moe_intermediate_size``, ...); ``n_routed_experts``
is the experts held here and ``published.n_routed_experts`` the router's
width.  Every projection is an int8 ROM trunk (d_in x d_out) beside a
branch: compress d_in -> d_in/D, then ``core @ U`` (d_in/D x d_out),
which serving derives once (``rebranch.serving_branch``) and reads in
bfloat16.  An expert stack shares ``C`` and holds one ``core @ U`` per
expert.  A multiply-accumulate is 2 operations.

Latent attention in the direct form: each layer caches
``kv_lora_rank + qk_rope_head_dim`` values a position and expands the
latents it attends over through ``kv_b_proj`` (r -> heads x (nope + v)).
"""

from __future__ import annotations

BF16 = 2
F32 = 4


def _dims(body: dict) -> dict:
    h = body["num_attention_heads"]
    dn, dr, dv = (body["qk_nope_head_dim"], body["qk_rope_head_dim"],
                  body["v_head_dim"])
    r, d = body["kv_lora_rank"], body["hidden_size"]
    return {"d": d, "h": h, "dn": dn, "dr": dr, "dv": dv, "r": r,
            "L": body["num_hidden_layers"],
            "dense": body["first_k_dense_replace"],
            "ff": body["intermediate_size"],
            "eff": body["moe_intermediate_size"],
            "shared": body["n_shared_experts"] * body["moe_intermediate_size"],
            "held": body["n_routed_experts"],
            "E": body["published"]["n_routed_experts"],
            "k": body["num_experts_per_tok"], "V": body["vocab_size"],
            "D": body["rebranch"]["d_ratio"],
            "U": body["rebranch"]["u_ratio"]}


def _mlp(d: int, ff: int) -> list[tuple[int, int]]:
    return [(d, ff), (d, ff), (ff, d)]


def attention_projections(body: dict) -> list[tuple[int, int]]:
    """(d_in, d_out) of one layer's attention projections applied to
    the token: q_proj, kv_a_proj_with_mqa, o_proj (``kv_b_proj``
    expands cached latents: :func:`expansion`)."""
    m = _dims(body)
    return [(m["d"], m["h"] * (m["dn"] + m["dr"])),
            (m["d"], m["r"] + m["dr"]), (m["h"] * m["dv"], m["d"])]


def expansion(body: dict) -> tuple[int, int]:
    """(d_in, d_out) of ``kv_b_proj``: one latent to every head's
    k_nope and v."""
    m = _dims(body)
    return (m["r"], m["h"] * (m["dn"] + m["dv"]))


def _linear_ops(m: dict, d_in: int, d_out: int) -> tuple[int, int]:
    """(int8 trunk ops, float branch ops) of one row through one site."""
    c = max(1, d_in // m["D"])
    return 2 * d_in * d_out, 2 * (d_in * c + c * d_out)


def _work(body: dict, context: float, expanded: float,
          expert_rows: float) -> dict:
    """One token: every projection once, ``expanded`` latents through
    ``kv_b_proj``, attention over ``context`` positions, and
    ``expert_rows`` routed-expert passes per MoE layer."""
    m = _dims(body)
    trunk = branch = 0.0

    def add(d_in, d_out, times=1.0):
        nonlocal trunk, branch
        t, b = _linear_ops(m, d_in, d_out)
        trunk += times * t
        branch += times * b

    for d_in, d_out in attention_projections(body):
        add(d_in, d_out, m["L"])
    add(*expansion(body), m["L"] * expanded)
    attn = m["L"] * 2 * m["h"] * (m["dn"] + m["dr"] + m["dv"]) * context
    moe_layers = m["L"] - m["dense"]
    for d_in, d_out in _mlp(m["d"], m["ff"]):
        add(d_in, d_out, m["dense"])
    for d_in, d_out in _mlp(m["d"], m["shared"]):
        add(d_in, d_out, moe_layers)
    for d_in, d_out in _mlp(m["d"], m["eff"]):
        add(d_in, d_out, moe_layers * expert_rows)
    router = moe_layers * 2 * m["d"] * m["E"]
    add(m["d"], m["V"])
    return {"int8_ops": trunk, "float_ops": branch + attn + router}


def token_work(body: dict, context: float) -> dict:
    """The model's operations for one token attending to ``context``
    positions: each projection once (its own latent expanded once), and
    the expected share of its k routed experts held here (k x held /
    E)."""
    m = _dims(body)
    return _work(body, context, 1.0, m["k"] * m["held"] / m["E"])


def param_bytes(body: dict) -> int:
    """Bytes the served decode step reads of the parameters: int8 trunks
    with float32 per-channel scales, the served branch in bfloat16 (``C``
    and ``core @ U``; one ``core @ U`` per held expert), the router and
    the norms in float32.  The embedding's rows are looked up, not
    read whole, and are left out."""
    m = _dims(body)

    def site(d_in, d_out, experts=1):
        c = max(1, d_in // m["D"])
        return experts * (d_in * d_out + F32 * d_out + BF16 * c * d_out) \
            + BF16 * d_in * c

    per_layer = sum(site(a, b) for a, b in attention_projections(body))
    per_layer += site(*expansion(body))
    per_layer += F32 * (m["r"] + 2 * m["d"])                    # norms
    moe_layers = m["L"] - m["dense"]
    total = m["L"] * per_layer
    total += m["dense"] * sum(site(a, b) for a, b in _mlp(m["d"], m["ff"]))
    total += moe_layers * (
        sum(site(a, b) for a, b in _mlp(m["d"], m["shared"]))
        + sum(site(a, b, m["held"]) for a, b in _mlp(m["d"], m["eff"]))
        + F32 * m["d"] * m["E"])
    return int(total + site(m["d"], m["V"]) + F32 * m["d"])


def latent_bytes_per_position(body: dict, itemsize: int) -> int:
    """Bytes of the latent cache one position holds, all layers."""
    m = _dims(body)
    return m["L"] * (m["r"] + m["dr"]) * itemsize


def decode_step_work(body: dict, live: list[int], itemsize: int) -> dict:
    """One batched decode step: the rows in ``live`` (each row's cached
    length) each make one token.  Operations: each row's token, the
    expansion of every live latent it attends to (the direct form
    expands them all, every step) and its expected held-expert share.
    Bytes: every served parameter once, the live latents of those rows
    read, one new position written."""
    m = _dims(body)
    ops = {"int8_ops": 0.0, "float_ops": 0.0}
    for n in live:
        w = _work(body, n + 1, n + 1, m["k"] * m["held"] / m["E"])
        ops["int8_ops"] += w["int8_ops"]
        ops["float_ops"] += w["float_ops"]
    per = latent_bytes_per_position(body, itemsize)
    ops["bytes"] = param_bytes(body) + per * sum(n + 1 for n in live)
    return ops


def program_step_ops(body: dict, rows: int, horizon: int) -> dict:
    """Operations the decode step program computes at its shapes: every
    row expands and attends over the whole ``horizon`` (masked positions
    too), and every held expert runs over every row.  Kept to check the
    formulas against a compiled program's own count."""
    m = _dims(body)
    w = _work(body, horizon, horizon, m["held"])
    return {k: rows * v for k, v in w.items()}
