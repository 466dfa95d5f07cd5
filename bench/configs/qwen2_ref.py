"""Plain reference of a ReBranch Qwen2-style decoder (text path).

Written from the published architecture (Qwen2 / Qwen2-VL, arXiv
2407.10671 and 2409.12191: pre-norm RMSNorm, grouped-query attention
with biased q/k/v and rotary position embedding, SwiGLU MLP, tied
embedding readout; for text the three M-RoPE sections share one
position, which is plain RoPE) and from YOLoC's ReBranch (§3.2): every
projection is

    y = (x_q @ W_q) * s_row * w_scale + ((x @ C) @ core) @ U (+ b)

with x quantised per row (token) to int8, W_q the int8 ROM codes with
per-output-channel scales, and C / core / U the float branch.  The
embedding table is int8 codes with a scale per token; the readout is
the dequantised table's transpose.  It imports nothing of the program.

Float work runs in float32 at ``highest`` matmul precision, over the
whole sequence at once (no cache): the logits of position t depend on
positions <= t alone.  ``trunk_bits=4`` is the control: every trunk in
int4 (weights and activations), the nearest precision below int8.
"""

from __future__ import annotations

import functools

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


def linear(p, x, bits):
    """One ReBranch projection of x [S, d_in]."""
    rom, sram = p["rom"], p["sram"]
    codes, scale = rom["w_q"], rom["w_scale"]
    if bits != 8:
        codes, scale = _quant(codes.astype(jnp.float32) * scale, 0, bits)
    x_q, s_row = _quant(x, -1, bits)
    acc = jax.lax.dot_general(x_q.astype(jnp.int8), codes.astype(jnp.int8),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * s_row * scale
    y = y + _mm(_mm(_mm(x, rom["C"]), sram["core"]), rom["U"])
    if "b" in sram:
        y = y + sram["b"]
    return y


def _rope(x, theta):
    """x [S, H, Dh]; rotation of the two halves by position * freq."""
    s, _, dh = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, dh, 2) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, dims, bits):
    h, kv, dh, eps, theta = dims
    s = x.shape[0]
    a = _rmsnorm(x, lp["ln1"]["sram"]["scale"], eps)
    q = linear(lp["attn"]["q"], a, bits).reshape(s, h, dh)
    k = linear(lp["attn"]["k"], a, bits).reshape(s, kv, dh)
    v = linear(lp["attn"]["v"], a, bits).reshape(s, kv, dh)
    q, k = _rope(q, theta), _rope(k, theta)
    qg = q.reshape(s, kv, h // kv, dh)
    scores = jnp.einsum("sgrd,tgd->grst", qg, k, precision=HIGHEST) \
        / np.sqrt(dh)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("grst,tgd->sgrd", probs, v, precision=HIGHEST)
    x = x + linear(lp["attn"]["o"], att.reshape(s, h * dh), bits)
    m = _rmsnorm(x, lp["ln2"]["sram"]["scale"], eps)
    g = linear(lp["mlp"]["gate"], m, bits)
    u = linear(lp["mlp"]["up"], m, bits)
    return x + linear(lp["mlp"]["down"], jax.nn.silu(g) * u, bits)


@functools.partial(jax.jit, static_argnames=("dims", "bits"))
def _hidden(params, ids, dims, bits):
    emb = params["embed"]["rom"]
    x = emb["table_q"][ids].astype(jnp.float32) * emb["table_scale"][ids]

    def body(xx, lp):
        return _layer(xx, lp, dims, bits), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _rmsnorm(x, params["ln_f"]["sram"]["scale"], dims[3])


@jax.jit
def _readout(params, rows):
    emb = params["embed"]["rom"]
    table = emb["table_q"].astype(jnp.float32) * emb["table_scale"]
    return jnp.einsum("sd,vd->sv", rows, table, precision=HIGHEST)


def logits(params, ids, positions, body: dict, trunk_bits: int = 8,
           pad_to: int | None = None, block: int = 256) -> np.ndarray:
    """Logits [len(positions), V] of the sequence ``ids`` at
    ``positions`` (each the logits that predict the next token).  The
    sequence is padded to ``pad_to`` (one program for every length);
    the padding sits after every position asked for."""
    h = body["num_attention_heads"]
    dims = (h, body["num_key_value_heads"],
            body.get("head_dim") or body["hidden_size"] // h,
            float(body["rms_norm_eps"]), float(body["rope_theta"]))
    ids = np.asarray(ids, np.int32)
    n = pad_to or ids.size
    padded = np.zeros(n, np.int32)
    padded[:ids.size] = ids
    x = _hidden(params, jnp.asarray(padded), dims, trunk_bits)
    pos = np.asarray(positions)
    # whole blocks only (the last padded with its final position): one
    # readout program for every length
    padded_pos = np.resize(pos, -(-pos.size // block) * block)
    padded_pos[pos.size:] = pos[-1]
    out = [np.asarray(_readout(params,
                               x[jnp.asarray(padded_pos[i:i + block])]))
           for i in range(0, padded_pos.size, block)]
    return np.concatenate(out, 0)[:pos.size]
