"""Plain reference of a ReBranch DeepSeek-V2 decoder, one chip's share.

Written from DeepSeek-V2 (arXiv:2405.04434): §2.1 multi-head latent
attention, §2.2 DeepSeekMoE; from YaRN (arXiv:2309.00071) for the
rotary frequencies and the attention scale; and from YOLoC's ReBranch
(§3.2): every projection is

    y = (x_q @ W_q) * s_row * w_scale + ((x @ C) @ core) @ U

with x quantised per row (token) to int8, W_q the int8 ROM codes with
per-output-channel scales, and C / core / U the float branch (an expert
stack shares C and U; each expert has its own core).  The embedding
table is int8 codes with a scale per token.  It imports nothing of the
program.

A layer, for hidden states h [S, d]:

    c    = RMSNorm(W_kva h)[:, :R]          (R = kv_lora_rank)
    k_pe = RoPE(W_kva h)[:, R:]             (one rotated key, all heads)
    [k_nope, v]_i = (W_kvb c)_i             (per head i)
    [q_nope, q_pe]_i = (W_q h)_i, q_pe rotated
    o = W_o concat_i softmax(scale * [q_nope, q_pe]_i . [k_nope_i, k_pe]) v_i
    scale = mscale**2 / sqrt(nope + rope), mscale = 0.1 m ln(factor) + 1

then h + o; the next half is a SwiGLU MLP (the leading dense layers) or
the MoE: softmax router over all ``published.n_routed_experts``,
greedy top-k, gates renormalised only if ``norm_topk_prob``, times
``routed_scaling_factor``, and the shared experts (one MLP of
``n_shared_experts * moe_intermediate_size``) added ungated.

Departures from the paper, each shared with the program under test:

* One chip's share of an expert-parallel deployment: of the routed
  experts only those held here (``n_routed_experts``, the first ones)
  contribute; the others' part of each MoE output is left out.  The
  vocabulary is its first ``vocab_size`` rows (embedding and readout).
* Rotary pairs are the two halves of the 64 rotated dimensions, not
  DeepSeek's interleaved pairs (a fixed permutation of the q and kv_a
  outputs' rope columns, which random weights do not see).
* Quantised trunks (above); RMSNorm, softmax and the router in float32.

Float work runs in float32 at ``highest`` matmul precision, over the
whole sequence at once (no cache): the logits of position t depend on
positions <= t alone.  ``trunk_bits=4`` is the control: every trunk in
int4 (weights and activations), the nearest precision below int8.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _quant(x, axis, bits):
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-8) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax), scale


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _trunk(x, codes, scale, bits):
    """Quantised x [S, d_in] times int codes [d_in, d_out] (or an expert
    stack's [E, d_in, d_out], giving [E, S, d_out])."""
    if bits != 8:
        codes, scale = _quant(codes.astype(jnp.float32) * scale, -2, bits)
    x_q, s_row = _quant(x, -1, bits)
    eq = "sd,df->sf" if codes.ndim == 2 else "sd,edf->esf"
    if x.ndim == 3:
        eq = "esd,edf->esf"
    acc = jnp.einsum(eq, x_q.astype(jnp.int8), codes.astype(jnp.int8),
                     preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * s_row * scale


def linear(p, x, bits):
    """One ReBranch projection of x [S, d_in]."""
    rom, sram = p["rom"], p["sram"]
    y = _trunk(x, rom["w_q"], rom["w_scale"], bits)
    return y + _mm(_mm(_mm(x, rom["C"]), sram["core"]), rom["U"])


def experts(p, x, bits):
    """Each held expert's projection: x [S, d] or [E, S, d] -> [E, S, f]."""
    rom, sram = p["rom"], p["sram"]
    y = _trunk(x, rom["w_q"], rom["w_scale"], bits)
    t1 = _mm(x, rom["C"])
    branch = jnp.einsum("sc,ecu->esu" if x.ndim == 2 else "esc,ecu->esu",
                        t1, sram["core"], precision=HIGHEST)
    return y + _mm(branch, rom["U"])


def _swiglu(p, x, bits):
    return linear(p["down"], jax.nn.silu(linear(p["gate"], x, bits))
                  * linear(p["up"], x, bits), bits)


def yarn(body):
    """Inverse frequencies of the rotated dimensions and YaRN's
    attention mscale (arXiv:2309.00071 §3; DeepSeek-V2's settings)."""
    dim, base = body["qk_rope_head_dim"], float(body["rope_theta"])
    y = body["rope_scaling"]
    inv = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    factor, orig = float(y["factor"]), y["original_max_position_embeddings"]

    def rotations_to_dim(r):           # dims that turn r times in orig
        return dim * math.log(orig / (r * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(rotations_to_dim(y["beta_fast"])), 0)
    hi = min(math.ceil(rotations_to_dim(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    inv = inv * (1 - ramp) + (inv / factor) * ramp

    def mscale(m):
        return 0.1 * m * math.log(factor) + 1.0 if m else 1.0

    rot_scale = mscale(y["mscale"]) / mscale(y["mscale_all_dim"])
    return inv, rot_scale, mscale(y["mscale_all_dim"])


def _rope(x, s, inv, rot_scale):
    """x [S, ..., dr]: the two halves rotated by position * freq."""
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None]
    shape = (s,) + (1,) * (x.ndim - 2) + (inv.size,)
    cos = (jnp.cos(ang) * rot_scale).reshape(shape)
    sin = (jnp.sin(ang) * rot_scale).reshape(shape)
    x1, x2 = x[..., :inv.size], x[..., inv.size:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, ap, dims, rope, bits):
    h, dn, dr, dv, r, eps, scale = dims
    inv, rot_scale = rope
    s = x.shape[0]
    q = linear(ap["q_proj"], x, bits).reshape(s, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], s, inv,
                                            rot_scale)], -1)
    kv_a = linear(ap["kv_a_proj_with_mqa"], x, bits)
    c = _rmsnorm(kv_a[:, :r], ap["kv_a_layernorm"]["sram"]["scale"], eps)
    k_pe = _rope(kv_a[:, r:], s, inv, rot_scale)                 # [S, dr]
    kv = linear(ap["kv_b_proj"], c, bits).reshape(s, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe[:, None], (s, h, dr))], -1)
    scores = jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST) * scale
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    att = jnp.einsum("hst,thd->shd", probs, kv[..., dn:], precision=HIGHEST)
    return linear(ap["o_proj"], att.reshape(s, h * dv), bits)


def _moe(x, mp, moe_dims, bits):
    n_all, k, norm, routed_scale = moe_dims
    held = mp["experts"]["gate"]["rom"]["w_q"].shape[0]
    probs = jax.nn.softmax(_mm(x, mp["router"]["sram"]["w"]), -1)
    gates, idx = jax.lax.top_k(probs, k)                    # greedy top-k
    if norm:
        gates = gates / gates.sum(-1, keepdims=True)
    gates = gates * routed_scale
    # each token's gate for each held expert (0 where not routed there)
    g = jnp.sum(jnp.where(idx[..., None] == jnp.arange(held), gates[..., None],
                          0.0), 1)                          # [S, held]
    ex = mp["experts"]
    hmid = jax.nn.silu(experts(ex["gate"], x, bits)) * experts(ex["up"], x,
                                                               bits)
    out = experts(ex["down"], hmid, bits)                   # [held, S, d]
    y = jnp.einsum("se,esd->sd", g, out, precision=HIGHEST)
    return y + _swiglu(mp["shared"], x, bits)


def _layer(x, lp, dims, rope, moe_dims, bits):
    eps = dims[5]
    x = x + _attention(_rmsnorm(x, lp["ln1"]["sram"]["scale"], eps),
                       lp["attn"], dims, rope, bits)
    m = _rmsnorm(x, lp["ln2"]["sram"]["scale"], eps)
    if "moe" in lp:
        return x + _moe(m, lp["moe"], moe_dims, bits)
    return x + _swiglu(lp["mlp"], m, bits)


@functools.partial(jax.jit, static_argnames=("dims", "rope", "moe_dims",
                                             "bits"))
def _hidden(params, ids, dims, rope, moe_dims, bits):
    emb = params["embed"]["rom"]
    x = emb["table_q"][ids].astype(jnp.float32) * emb["table_scale"][ids]
    rope = (np.asarray(rope[0]), rope[1])

    def body(xx, lp):
        return _layer(xx, lp, dims, rope, moe_dims, bits), None

    for stack in ("dense_layers", "layers"):
        if stack in params:
            x, _ = jax.lax.scan(body, x, params[stack])
    return _rmsnorm(x, params["ln_f"]["sram"]["scale"], dims[5])


@functools.partial(jax.jit, static_argnames=("bits",))
def _readout(params, rows, bits):
    return linear(params["lm_head"], rows, bits)


def logits(params, ids, positions, body: dict, trunk_bits: int = 8,
           pad_to: int | None = None, block: int = 256) -> np.ndarray:
    """Logits [len(positions), V] of the sequence ``ids`` at
    ``positions`` (each the logits that predict the next token).  The
    sequence is padded to ``pad_to`` (one program for every length);
    the padding sits after every position asked for."""
    inv, rot_scale, mscale = yarn(body)
    dn, dr = body["qk_nope_head_dim"], body["qk_rope_head_dim"]
    dims = (body["num_attention_heads"], dn, dr, body["v_head_dim"],
            body["kv_lora_rank"], float(body["rms_norm_eps"]),
            mscale * mscale / math.sqrt(dn + dr))
    moe_dims = (body["published"]["n_routed_experts"],
                body["num_experts_per_tok"], bool(body["norm_topk_prob"]),
                float(body["routed_scaling_factor"]))
    ids = np.asarray(ids, np.int32)
    n = pad_to or ids.size
    padded = np.zeros(n, np.int32)
    padded[:ids.size] = ids
    x = _hidden(params, jnp.asarray(padded), dims,
                (tuple(float(f) for f in inv), float(rot_scale)), moe_dims,
                trunk_bits)
    pos = np.asarray(positions)
    # whole blocks only (the last padded with its final position): one
    # readout program for every length
    padded_pos = np.resize(pos, -(-pos.size // block) * block)
    padded_pos[pos.size:] = pos[-1]
    out = [np.asarray(_readout(params, x[jnp.asarray(padded_pos[i:i + block])],
                               trunk_bits))
           for i in range(0, padded_pos.size, block)]
    return np.concatenate(out, 0)[:pos.size]
