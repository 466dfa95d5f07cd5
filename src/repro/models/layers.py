"""Shared model components (ReBranch-aware, sharding-annotated).

Every large linear map goes through core.rebranch (frozen int8 ROM trunk +
trainable branch); norms, biases and routers are small and stay trainable
("SRAM").  Embedding tables are ROM (int8 + scale) — lookups dequantise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant, rebranch
from repro.distributed.sharding import shard
from repro.models.config import ArchConfig


def _dt(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int):
    return {"sram": {"scale": jnp.ones((d,), jnp.float32)}}


def apply_rmsnorm(params, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["sram"]["scale"]).astype(dtype)


# ---------------------------------------------------------------------------
# embeddings (ROM: int8 table + scale)
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, cfg: ArchConfig):
    table = jax.random.normal(key, (vocab, d), jnp.float32)
    t_q, t_scale = quant.quantize_weights(table, axis=1)   # per-token scale
    return {"rom": {"table_q": t_q, "table_scale": t_scale}}


def apply_embedding(params, ids, cfg: ArchConfig):
    with jax.named_scope("embed"):
        t_q = params["rom"]["table_q"]
        t_s = params["rom"]["table_scale"]
        return t_q[ids].astype(_dt(cfg)) * t_s[ids].astype(_dt(cfg))


def embedding_as_logits(params, x, cfg: ArchConfig):
    """Tied-embedding readout: x @ dequant(table)^T."""
    t_q = params["rom"]["table_q"]
    t_s = params["rom"]["table_scale"]
    w = t_q.astype(x.dtype) * t_s.astype(x.dtype)          # [V, d]
    return jnp.einsum("...d,vd->...v", x, w)


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE and qwen2-vl M-RoPE)
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float = 10_000.0, mrope: bool = False):
    """x: [B, S, H, Dh]; positions: [B, S] (or [B, S, 3] for M-RoPE).

    M-RoPE (qwen2-vl): the rotary dimensions are split into 3 sections
    (temporal / height / width) fed by 3 position streams.  For text-only
    streams all three positions coincide and M-RoPE == RoPE.
    """
    dh = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(dh, theta), jnp.float32)  # [dh/2]
    if mrope:
        if positions.ndim == 2:                      # text-only degenerate
            positions = jnp.broadcast_to(positions[..., None],
                                         (*positions.shape, 3))
        n = dh // 2
        # section split 2:1:1 over rotary dims (t, h, w)
        sec = np.array([n - 2 * (n // 4), n // 4, n // 4])
        sel = np.repeat(np.arange(3), sec)           # [dh/2] -> section id
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32),
            jnp.broadcast_to(jnp.asarray(sel)[None, None, :],
                             (*positions.shape[:2], n)).astype(jnp.int32),
            axis=-1)                                  # [B, S, dh/2]
        angles = pos * freqs[None, None, :]
    else:
        angles = positions.astype(jnp.float32)[..., None] * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA + KV cache + chunked causal / sliding window)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig):
    ks = jax.random.split(key, 4)
    spec = cfg.rebranch
    h, kv, dh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    return {
        "q": rebranch.init_linear(ks[0], d, h * dh, spec, use_bias=cfg.qkv_bias),
        "k": rebranch.init_linear(ks[1], d, kv * dh, spec, use_bias=cfg.qkv_bias),
        "v": rebranch.init_linear(ks[2], d, kv * dh, spec, use_bias=cfg.qkv_bias),
        "o": rebranch.init_linear(ks[3], h * dh, d, spec),
    }


def _chunked_causal_attention(q, k, v, chunk: int, window: int = 0,
                              kv_offset: int = 0):
    """Memory-bounded causal attention via online softmax over KV chunks.

    q: [B, Sq, H, Dh], k/v: [B, Skv, KV, Dh].  O(Sq * chunk) live memory
    instead of O(Sq * Skv) — required for the 32k prefill shapes.
    window > 0 restricts to a sliding window (hymba SWA layers).
    """
    b, sq, h, dh = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    scale = 1.0 / np.sqrt(dh)
    q = q.astype(jnp.float32) * scale
    qpos = kv_offset + jnp.arange(sq)

    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, chunk, kvh, dh).astype(jnp.float32)
    vc = v.reshape(b, n_chunks, chunk, kvh, dh).astype(jnp.float32)
    kc = jnp.moveaxis(kc, 1, 0)       # [C, B, chunk, KV, Dh]
    vc = jnp.moveaxis(vc, 1, 0)

    def step(carry, inputs):
        m, l, acc = carry              # [B,H,Sq], [B,H,Sq], [B,H,Sq,Dh]
        kblk, vblk, cidx = inputs
        kpos = cidx * chunk + jnp.arange(chunk)
        # scores: [B, H, Sq, chunk] (q heads grouped onto kv heads)
        qg = q.reshape(b, sq, kvh, rep, dh)
        s = jnp.einsum("bsgrd,bcgd->bgrsc", qg, kblk)
        s = s.reshape(b, kvh * rep, sq, chunk)
        mask = kpos[None, :] <= qpos[:, None]                  # causal
        mask &= kpos[None, :] < skv                            # padding
        if window:
            mask &= kpos[None, :] > (qpos[:, None] - window)
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bgrsc,bcgd->bgrsd",
                        p.reshape(b, kvh, rep, sq, chunk), vblk)
        acc_new = acc * corr[..., None] + pv.reshape(b, kvh * rep, sq, dh)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dh), jnp.float32)
    # flash-attention-style backward: recompute scores/probs per chunk in
    # the bwd pass instead of stacking per-step residuals across the scan
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, a0), (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2)     # [B, Sq, H, Dh]


def _gather_paged(leaf, table):
    """Materialise the logical [B, S, KV, Dh] view of a paged cache leaf.

    leaf: [P, bs, KV, Dh] physical blocks; table: [B, NB] block ids.
    The gathered view is identical (bit for bit, at every valid
    position) to the dense row the same request would hold in a
    :class:`~repro.serve.pool.SlotPool`, so attention math downstream is
    unchanged — paging moves bytes, never bits.  Positions beyond a
    row's length read whatever the un-granted blocks hold; they are
    masked by the validity count exactly like stale dense rows.
    """
    b, nb = table.shape
    bs = leaf.shape[1]
    view = leaf[table]                       # [B, NB, bs, KV, Dh]
    return view.reshape(b, nb * bs, *leaf.shape[2:])


def _verify_attention(q, k_cache, v_cache, length, s_max):
    """Speculative-verify attention: S queries against one cache view.

    q: [B, S, H, Dh]; the cache already holds this block's KV writes at
    positions ``length .. length+S-1``.  Query j may see positions
    ``< length+1+j`` — its own entry and everything before it — and the
    drafted FUTURE entries are masked out.  Implemented as S calls to
    :func:`_decode_attention` (one per query position) inside one trace,
    so each query's softmax runs over exactly the shapes the plain
    decode path uses: accepted speculative tokens are bit-identical to
    sequential decode by construction, not by accident of einsum
    scheduling.
    """
    outs = [
        _decode_attention(q[:, j:j + 1], k_cache, v_cache,
                          jnp.minimum(length + 1 + j, s_max))
        for j in range(q.shape[1])
    ]
    return jnp.concatenate(outs, axis=1)


def _decode_attention(q, k_cache, v_cache, valid_count):
    """Single-position attention against a (possibly ring-buffer) cache.

    q: [B, 1, H, Dh].  Attention over a *set* of cached entries is order-
    invariant (RoPE already encodes absolute positions), so ring-buffer
    eviction needs no re-ordering — just a validity mask.
    """
    b, _, h, dh = q.shape
    s_max, kvh = k_cache.shape[1], k_cache.shape[2]
    rep = h // kvh
    scale = 1.0 / np.sqrt(dh)
    s = jnp.einsum("bgrd,bcgd->bgrc",
                   (q.astype(jnp.float32) * scale)[:, 0].reshape(b, kvh, rep, dh),
                   k_cache.astype(jnp.float32))       # [B, KV, rep, S]
    pos = jnp.arange(s_max)
    mask = pos[None, :] < valid_count[:, None]        # [B, S]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrc,bcgd->bgrd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, dh)


def apply_attention(params, x, cfg: ArchConfig, layer_idx: int,
                    positions=None, cache=None, decode: bool = False):
    """Returns (out, new_cache_entry), under the named scope
    ``attention`` (its KV-cache write under ``kv_write``)."""
    with jax.named_scope("attention"):
        return _attention(params, x, cfg, layer_idx, positions, cache,
                          decode)


def _attention(params, x, cfg: ArchConfig, layer_idx: int, positions,
               cache, decode: bool):
    spec = cfg.rebranch
    b, s, d = x.shape
    h, kv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    window = 0 if cfg.uses_full_attention(layer_idx) else cfg.sliding_window

    # NOTE: no explicit q/k/v constraints — GSPMD propagates the projection
    # output sharding through the reshape; forcing head sharding here causes
    # involuntary remat when heads don't divide the model axis (gemma, yi).
    q = rebranch.apply_linear(params["q"], x, spec).reshape(b, s, h, dh)
    k = rebranch.apply_linear(params["k"], x, spec).reshape(b, s, kv, dh)
    v = rebranch.apply_linear(params["v"], x, spec).reshape(b, s, kv, dh)

    paged = cache is not None and "table" in cache
    if positions is None:
        if decode and cache is not None:
            # [B, S]: each row's tokens extend its own length.  S is 1
            # for plain decode (the arange term is an exact integer +0)
            # and the block width for speculative verify.
            positions = cache["length"][:, None] + jnp.arange(s)[None]
        elif cache is not None:
            # prefill CONTINUATION: tokens extend the cache at its
            # current per-row length (fresh cache -> offset 0, the plain
            # prefill path, bit for bit)
            positions = cache["length"][:, None] + jnp.arange(s)[None]
        else:
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope)

    if decode:
        # s == 1: plain decode, one token per row.  s > 1: speculative
        # VERIFY — a k-token draft block per row, written entry by entry
        # (same scatter per position as k plain decode steps) and
        # attended with per-query validity, so accepted tokens are
        # bit-identical to sequential decode.  Verify requires a
        # full-horizon cache (no SWA ring: a wrap would overwrite
        # entries a rejected draft must roll back) — gated upstream by
        # ``api.supports_speculation``.
        assert cache is not None
        length = cache["length"]                               # [B]
        rows = jnp.arange(k.shape[0])
        k_cache, v_cache = cache["k"], cache["v"]
        if paged:
            # Paged KV: rows own BLOCKS, not whole horizon rows.  The
            # block table indirects each row's logical ring slot to a
            # physical (block, offset); the scatter writes one entry and
            # the gather materialises the logical view attention reads.
            # Free rows' table entries all point at the pool's trash
            # block, so their (masked, never-read) decode writes land
            # outside every live request's blocks.
            table = cache["table"]                     # [B, NB]
            bs = cache["k"].shape[1]
            s_max = table.shape[1] * bs
            with jax.named_scope("kv_write"):
                for j in range(s):
                    slot = (length + j) % s_max
                    pb = table[rows, slot // bs]       # [B] physical block
                    off = slot % bs
                    k_cache = k_cache.at[pb, off].set(
                        k[:, j].astype(k_cache.dtype))
                    v_cache = v_cache.at[pb, off].set(
                        v[:, j].astype(v_cache.dtype))
            k_view = _gather_paged(k_cache, table)
            v_view = _gather_paged(v_cache, table)
        else:
            s_max = cache["k"].shape[1]
            # Per-ROW ring slot: under continuous batching the rows of
            # one cache hold different sequences at different lengths,
            # so each row writes its own slot (a shared ``length[0]``
            # slot corrupts every row whose length differs from row 0's
            # — the new KV lands inside an already-valid slot and the
            # true slot stays stale).
            with jax.named_scope("kv_write"):
                for j in range(s):
                    slot = (length + j) % s_max   # [B] ring for SWA layers
                    k_cache = k_cache.at[rows, slot].set(
                        k[:, j].astype(k_cache.dtype))
                    v_cache = v_cache.at[rows, slot].set(
                        v[:, j].astype(v_cache.dtype))
            k_view, v_view = k_cache, v_cache
        if s == 1:
            valid = jnp.minimum(length + 1, s_max)
            out = _decode_attention(q, k_view, v_view, valid)
        else:
            out = _verify_attention(q, k_view, v_view, length, s_max)
        new_cache = {**cache, "k": k_cache, "v": v_cache,
                     "length": length + s}
    else:
        if paged:
            raise ValueError(
                "prefill cannot run against a paged cache (physical "
                "blocks have no per-row horizon to fill); prefill into "
                "a dense batch=1 cache and adopt the row into the "
                "paged pool (serve.pool.PagedPool.adopt)")
        if cache is not None and s < cache["k"].shape[1]:
            # Prefill against a cache: attend over the UPDATED cache view
            # (cached prefix ++ this chunk at its offset), so a prompt
            # split into chunks across scheduler ticks sees exactly the
            # keys a solo whole-prompt prefill would.  For a fresh cache
            # (offset 0) this is bit-identical to attending over the
            # chunk alone: positions beyond the chunk hold zeros and are
            # causally masked, and masked entries contribute exact zeros
            # to the online softmax.  Offset is length[0]: continuation
            # assumes uniform row lengths (admission prefills are B=1).
            offset = cache["length"][0]
            k_att = jax.lax.dynamic_update_slice_in_dim(
                cache["k"].astype(k.dtype), k, offset, axis=1)
            v_att = jax.lax.dynamic_update_slice_in_dim(
                cache["v"].astype(v.dtype), v, offset, axis=1)
            out = _chunked_causal_attention(
                q, k_att, v_att, cfg.attn_chunk, window, kv_offset=offset)
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), offset, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), offset, axis=1)
            new_cache = {"k": k_cache, "v": v_cache,
                         "length": cache["length"] + s}
        else:
            out = _chunked_causal_attention(q, k, v, cfg.attn_chunk, window)
            if cache is not None:    # prompt >= horizon: SWA ring fill
                s_max = cache["k"].shape[1]
                # keep the window tail, laid out so that token t sits at
                # slot t % s_max (decode continues the ring); chunked
                # continuation never reaches here (total <= horizon).
                k_w = jnp.roll(k[:, -s_max:], s % s_max, axis=1)
                v_w = jnp.roll(v[:, -s_max:], s % s_max, axis=1)
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k_w.astype(cache["k"].dtype), 0, axis=1)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v_w.astype(cache["v"].dtype), 0, axis=1)
                new_cache = {"k": k_cache, "v": v_cache,
                             "length": cache["length"] + s}
            else:
                new_cache = None

    out = out.astype(x.dtype).reshape(b, s, h * dh)
    out = rebranch.apply_linear(params["o"], out, spec,
                                t1_axes=("batch", "seq", "mlp"),
                                out_axes=("batch", "seq_sp", None))
    # seq_sp BEFORE the residual add: converts the row-parallel partial-sum
    # all-reduce into a reduce-scatter (16x less wire on a 16-way axis)
    return shard(out, "batch", "seq_sp", None), new_cache


def init_attention_cache(cfg: ArchConfig, batch: int, max_len: int,
                         layer_idx: int, dtype=jnp.bfloat16):
    """SWA layers get a ring buffer of window size; full-attention layers
    keep the whole horizon."""
    window = (0 if cfg.uses_full_attention(layer_idx)
              else cfg.sliding_window)
    s = max_len if window == 0 else min(max_len, window)
    return {
        "k": jnp.zeros((batch, s, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, s, cfg.num_kv_heads, cfg.head_dim), dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def init_paged_attention_cache(cfg: ArchConfig, rows: int, n_blocks: int,
                               block_size: int, max_len: int,
                               dtype=jnp.bfloat16):
    """One layer of a PAGED KV cache: physical blocks + a block table.

    ``k``/``v`` hold ``n_blocks`` physical blocks of ``block_size``
    positions each, shared by every row; ``table`` maps (row, logical
    block) -> physical block id and is owned by the pool (the model only
    reads it).  The logical horizon per row is
    ``table.shape[1] * block_size == max_len`` — ``block_size`` must
    divide ``max_len`` so the gathered view has exactly the dense
    cache's shape (same softmax geometry = same bits).  Table entries
    are initialised to the LAST block, which the pool reserves as the
    trash block for free rows' masked decode writes.
    """
    if max_len % block_size:
        raise ValueError(
            f"block_size {block_size} does not divide max_len {max_len}; "
            f"the gathered paged view must have exactly the dense cache "
            f"shape (same attention geometry = same bits)")
    if not cfg.uses_full_attention(layer_idx=0) or cfg.sliding_window:
        raise ValueError(
            f"paged KV requires a uniform full-attention horizon; "
            f"{cfg.name!r} has sliding_window={cfg.sliding_window} "
            f"(ring caches smaller than max_len cannot share one block "
            f"table) — serve this config over a dense SlotPool")
    nb = max_len // block_size
    return {
        "k": jnp.zeros((n_blocks, block_size, cfg.num_kv_heads,
                        cfg.head_dim), dtype),
        "v": jnp.zeros((n_blocks, block_size, cfg.num_kv_heads,
                        cfg.head_dim), dtype),
        "length": jnp.zeros((rows,), jnp.int32),
        "table": jnp.full((rows, nb), n_blocks - 1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / GELU)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig, d_ff: int | None = None):
    spec = cfg.rebranch
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "gate": rebranch.init_linear(ks[0], d, ff, spec),
            "up": rebranch.init_linear(ks[1], d, ff, spec),
            "down": rebranch.init_linear(ks[2], ff, d, spec),
        }
    return {
        "up": rebranch.init_linear(ks[1], d, ff, spec),
        "down": rebranch.init_linear(ks[2], ff, d, spec),
    }


def apply_mlp(params, x, cfg: ArchConfig):
    spec = cfg.rebranch
    with jax.named_scope("mlp"):
        if cfg.mlp_type in ("swiglu", "geglu"):
            g = rebranch.apply_linear(params["gate"], x, spec)
            u = rebranch.apply_linear(params["up"], x, spec)
            act = jax.nn.silu(g) if cfg.mlp_type == "swiglu" \
                else jax.nn.gelu(g)
            h = act * u
        else:
            h = jax.nn.gelu(rebranch.apply_linear(params["up"], x, spec))
        h = shard(h, "batch", "seq", "mlp")
        return rebranch.apply_linear(params["down"], h, spec,
                                     t1_axes=("batch", "seq", "mlp"),
                                     out_axes=("batch", "seq_sp", None))
