"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

Per layer, the only state cached is ``W = kv_lora_rank + qk_rope_head_dim``
values a position: the normalised latent ``c = RMSNorm(W_kva h)[:R]``
and the rotated key half ``k_pe`` that every head shares.  Attention
expands the cached latents back to per-head keys and values,
``[k_nope, v] = W_kvb c`` (the direct form: each call expands every
position of its view), and the query of head ``i`` is ``[q_nope_i,
RoPE(q_pe_i)]`` straight from ``q_proj`` (no query LoRA).  The score
scale is ``mscale**2 / sqrt(qk_nope + qk_rope)``; the rotary
frequencies of the ``qk_rope_head_dim`` rotated dimensions follow YaRN
(arXiv:2309.00071) when ``cfg.rope_scaling`` is set.

Rotary pairs are the two halves of the rotated dimensions (the layout
of ``layers.apply_rope``), not DeepSeek's interleaved pairs: the two are
a fixed permutation of ``q_proj`` and ``kv_a_proj_with_mqa`` outputs.

Every projection (``q_proj``, ``kv_a_proj_with_mqa``, ``kv_b_proj``,
``o_proj``) is a ReBranch site; ``kv_a_layernorm`` is a small SRAM norm.

The cache of all layers is one stacked leaf ``latent``: dense
``[L, B, S, W]`` (prefill, dense pools) or paged ``[L, P, bs, W]``
physical blocks read through the pool's block table.  :func:`apply`
writes layer ``layer``'s new positions into it and reads its view, so
the stacked leaf can ride a scan's carry and each layer updates its own
slice in place.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rebranch
from repro.models import layers
from repro.models.config import ArchConfig


def latent_width(cfg: ArchConfig) -> int:
    """Values cached per position and layer."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg: ArchConfig) -> float:
    s = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        m = _yarn_mscale(y.factor, y.mscale_all_dim)
        s *= m * m
    return s


def rope_inv_freq(cfg: ArchConfig) -> np.ndarray:
    """Inverse frequencies of the rotated dimensions (float64, [dr/2]):
    plain RoPE, or YaRN's blend of interpolated and original ones."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    y = cfg.rope_scaling
    if y is None:
        return extra

    def corr(rot):
        return (dim * math.log(y.original_max_position_embeddings
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    lo = max(math.floor(corr(y.beta_fast)), 0)
    hi = min(math.ceil(corr(y.beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp                       # 1: the original frequency
    return extra / y.factor * (1.0 - keep) + extra * keep


def _rope_cos_sin(cfg: ArchConfig, positions):
    """cos, sin [B, S, dr/2] (YaRN's cos/sin scale, mscale over
    mscale_all_dim, multiplies both)."""
    inv = jnp.asarray(rope_inv_freq(cfg), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv
    y = cfg.rope_scaling
    m = 1.0 if y is None else (_yarn_mscale(y.factor, y.mscale)
                               / _yarn_mscale(y.factor, y.mscale_all_dim))
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def _rotate(x, cos, sin):
    """x [B, S, ..., dr], cos/sin [B, S, dr/2]: halves rotated."""
    extra = x.ndim - cos.ndim
    cos = cos.reshape(*cos.shape[:2], *([1] * extra), cos.shape[-1])
    sin = sin.reshape(*sin.shape[:2], *([1] * extra), sin.shape[-1])
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def init(key, cfg: ArchConfig):
    ks = jax.random.split(key, 4)
    spec, d, h = cfg.rebranch, cfg.d_model, cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    return {
        "q_proj": rebranch.init_linear(ks[0], d, h * (dn + dr), spec),
        "kv_a_proj_with_mqa": rebranch.init_linear(ks[1], d, r + dr, spec),
        "kv_a_layernorm": layers.init_rmsnorm(r),
        "kv_b_proj": rebranch.init_linear(ks[2], r, h * (dn + dv), spec),
        "o_proj": rebranch.init_linear(ks[3], h * dv, d, spec),
    }


def _attend(params, q_nope, q_pe, view, mask, cfg: ArchConfig):
    """Heads over a latent view.  q_nope [B, S, H, dn], q_pe [B, S, H,
    dr], view [B, T, W], mask [B, S, T] -> [B, S, H * dv]."""
    b, s, h, dn = q_nope.shape
    r, dv = cfg.kv_lora_rank, cfg.v_head_dim
    t = view.shape[1]
    dt = q_nope.dtype
    with jax.named_scope("latent_expand"):
        kv = rebranch.apply_linear(params["kv_b_proj"],
                                   view[..., :r].astype(dt), cfg.rebranch)
        kv = kv.reshape(b, t, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k_pe = view[..., r:].astype(dt)
    f32 = jnp.float32
    sc = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope,
                     preferred_element_type=f32)
          + jnp.einsum("bshd,btd->bhst", q_pe, k_pe,
                       preferred_element_type=f32)) * softmax_scale(cfg)
    sc = jnp.where(mask[:, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", p, v.astype(f32))
    return out.astype(dt).reshape(b, s, h * dv)


def apply(params, x, cfg: ArchConfig, positions, latent=None, layer=0,
          table=None, length=None, decode: bool = False):
    """One MLA layer under the named scope ``mla``.

    x [B, S, d]; positions [B, S].  ``latent`` is the stacked cache leaf
    of every layer (None: attend over this call's tokens alone, the
    training forward) and ``layer`` this layer's index in it.  Decode
    (S == 1) writes each row's entry at its ``length`` through the
    block ``table`` (paged) or at its row (dense) and attends over the
    row's first ``length + 1`` positions; prefill writes the chunk at
    ``length[0]`` of a dense cache (rows of one length) and attends
    causally over the updated view.  Returns (out, latent)."""
    with jax.named_scope("mla"):
        return _apply(params, x, cfg, positions, latent, layer, table,
                      length, decode)


def _apply(params, x, cfg, positions, latent, layer, table, length,
           decode):
    spec = cfg.rebranch
    b, s, _ = x.shape
    h, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r = cfg.kv_lora_rank
    cos, sin = _rope_cos_sin(cfg, positions)
    q = rebranch.apply_linear(params["q_proj"], x, spec)
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_pe = q[..., :dn], _rotate(q[..., dn:], cos, sin)
    kv = rebranch.apply_linear(params["kv_a_proj_with_mqa"], x, spec)
    c = layers.apply_rmsnorm(params["kv_a_layernorm"], kv[..., :r],
                             cfg.norm_eps)
    new = jnp.concatenate([c, _rotate(kv[..., r:], cos, sin)], -1)

    if latent is None:
        view = new
        mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        mask = jnp.broadcast_to(mask, (b, s, s))
    elif decode:
        if s != 1:
            raise ValueError(
                "latent attention decodes one token per row (speculative "
                "verify is not implemented for it)")
        rows = jnp.arange(b)
        entry = new[:, 0].astype(latent.dtype)
        with jax.named_scope("kv_write"):
            # a free row's length runs on past the horizon: its masked
            # write wraps, and (paged) lands in the trash block
            if table is not None:
                bs = latent.shape[2]
                horizon = table.shape[1] * bs
                slot = length % horizon
                pb = table[rows, slot // bs]
                latent = latent.at[layer, pb, slot % bs].set(entry)
            else:
                horizon = latent.shape[2]
                latent = latent.at[layer, rows, length % horizon].set(entry)
        if table is not None:
            view = latent[layer, table].reshape(b, horizon, -1)
        else:
            view = latent[layer]
        mask = (jnp.arange(horizon)[None, :] <= length[:, None])[:, None]
    else:
        offset = length[0]
        with jax.named_scope("kv_write"):
            latent = jax.lax.dynamic_update_slice(
                latent, new[None].astype(latent.dtype),
                (layer, 0, offset, 0))
        view = latent[layer]
        horizon = view.shape[1]
        qpos = offset + jnp.arange(s)
        mask = jnp.arange(horizon)[None, :] <= qpos[:, None]
        mask = jnp.broadcast_to(mask, (b, s, horizon))
    out = _attend(params, q_nope, q_pe, view, mask, cfg)
    out = rebranch.apply_linear(params["o_proj"], out, spec)
    return out, latent


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """Dense latent cache of every layer: ``latent`` [L, B, S, W] and
    ``length`` [L, B] (every layer holds the same lengths)."""
    return {"latent": jnp.zeros((cfg.num_layers, batch, max_len,
                                 latent_width(cfg)), dtype),
            "length": jnp.zeros((cfg.num_layers, batch), jnp.int32)}


def init_paged_cache(cfg: ArchConfig, rows: int, n_blocks: int,
                     block_size: int, max_len: int, dtype=jnp.bfloat16):
    """Paged latent cache: ``n_blocks`` physical blocks of every layer,
    ``length`` [L, rows] and the block ``table`` [L, rows, max_len /
    block_size], its entries on the last (trash) block."""
    if max_len % block_size:
        raise ValueError(
            f"block_size {block_size} does not divide max_len {max_len}")
    n = cfg.num_layers
    return {"latent": jnp.zeros((n, n_blocks, block_size,
                                 latent_width(cfg)), dtype),
            "length": jnp.zeros((n, rows), jnp.int32),
            "table": jnp.full((n, rows, max_len // block_size),
                              n_blocks - 1, jnp.int32)}
