"""Decoder-only LM: dense / GQA / VLM / multi-codebook-audio families.

One implementation covers musicgen-large (4-codebook audio tokens),
qwen2-vl-2b (M-RoPE + vision-embedding stub), yi-34b, qwen1.5-32b
(QKV bias), gemma-2b (GeGLU, head_dim 256, MQA), deepseek-67b.

API (shared by all families in the zoo):
  init(key, cfg)                                   -> params
  forward(params, batch, cfg)                      -> logits
  prefill(params, batch, cfg, cache)               -> (logits, cache)
  decode_step(params, tokens, cfg, cache)          -> (logits, cache)
  init_cache(cfg, batch, max_len)                  -> cache
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import dataclasses

from repro.core import rebranch
from repro.distributed.sharding import shard
from repro.models import layers, mla, moe
from repro.models.config import ArchConfig, spec_for


def site_cfg(cfg: ArchConfig, site: str) -> ArchConfig:
    """cfg with the resolved spec for ``site`` as its config-wide rebranch.

    The per-site mapping hook for components whose internals consult
    ``cfg.rebranch`` directly (attention, MLP, MoE, SSM blocks): the
    caller resolves the component's site through ``spec_for`` — which
    honours ancestor-prefix overrides, so a ``'blocks'`` override still
    governs every ``blocks.*`` sub-site — and hands the component a cfg
    carrying that spec.  scan-over-layers keeps blocks uniform across
    depth, so block sub-sites name components, not layer indices."""
    spec = spec_for(cfg, site)
    if spec is cfg.rebranch:
        return cfg
    return dataclasses.replace(cfg, rebranch=spec)


def _block_init(key, cfg: ArchConfig, dense: bool = False):
    """One layer's params; ``dense``: a leading dense layer of an MoE
    model (an MLP of width ``d_ff`` in place of the experts)."""
    k1, k2 = jax.random.split(key)
    attn_cfg = site_cfg(cfg, "blocks.attn")
    block = {
        "ln1": layers.init_rmsnorm(cfg.d_model),
        "attn": (mla.init(k1, attn_cfg) if cfg.mla
                 else layers.init_attention(k1, attn_cfg)),
        "ln2": layers.init_rmsnorm(cfg.d_model),
    }
    if cfg.family == "moe" and not dense:
        block["moe"] = moe.init_moe_block(k2, site_cfg(cfg, "blocks.moe"))
    else:
        block["mlp"] = layers.init_mlp(k2, site_cfg(cfg, "blocks.mlp"))
    return block


def _residual(params, x, cfg: ArchConfig, attend, serving: bool,
              live=None):
    """One block of either attention kind: ``x + attend(ln1(x))``, then
    the MLP or MoE on ``ln2`` of that.  ``attend(p, h) -> (out, cache)``
    runs the layer's attention.  Returns (x, cache, held-expert
    assignments or None)."""
    h, new_cache = attend(params["attn"],
                          layers.apply_rmsnorm(params["ln1"], x,
                                               cfg.norm_eps))
    x = x + h
    h2, got = _ffn(params, layers.apply_rmsnorm(params["ln2"], x,
                                                cfg.norm_eps),
                   cfg, serving, live)
    return x + h2, new_cache, got


def _block_apply(params, x, cfg: ArchConfig, layer_idx: int,
                 positions=None, cache=None, decode=False):
    def attend(p, h):
        return layers.apply_attention(
            p, h, site_cfg(cfg, "blocks.attn"), layer_idx,
            positions=positions, cache=cache, decode=decode)
    return _residual(params, x, cfg, attend, serving=cache is not None)[:2]


def _ffn(params, h, cfg: ArchConfig, serving: bool, live=None):
    """(MLP or MoE output, held-expert assignments or None).  Serving
    calls take the dropless held-expert MoE; the training forward the
    capacity dispatch (``models.moe``)."""
    if "moe" not in params:
        return layers.apply_mlp(params["mlp"], h,
                                site_cfg(cfg, "blocks.mlp")), None
    moe_cfg = site_cfg(cfg, "blocks.moe")
    if serving:
        return moe.apply_held_experts(params["moe"], h, moe_cfg, live)
    return moe.apply_moe_block(params["moe"], h, moe_cfg), None


def init(key, cfg: ArchConfig):
    keys = jax.random.split(key, cfg.num_layers + 3)
    nd = cfg.first_k_dense
    if cfg.scan_layers:
        # stacked per-layer params (leading L dim) -> lax.scan over layers:
        # compile time is O(1) in depth (deepseek-67b: 95 layers)
        blocks = jax.vmap(lambda k: _block_init(k, cfg))(
            jnp.stack(keys[1 + nd:cfg.num_layers + 1]))
    else:
        blocks = [_block_init(keys[i + 1], cfg)
                  for i in range(cfg.num_layers)]
    params = {
        "embed": layers.init_embedding(keys[0], cfg.vocab_size,
                                       cfg.d_model, cfg),
        "layers": blocks,
        "ln_f": layers.init_rmsnorm(cfg.d_model),
    }
    if nd:
        params["dense_layers"] = jax.vmap(
            lambda k: _block_init(k, cfg, dense=True))(
                jnp.stack(keys[1:1 + nd]))
    if cfg.num_codebooks:      # musicgen: per-codebook readout heads
        params["codebook_head"] = rebranch.init_linear(
            keys[-1], cfg.d_model, cfg.num_codebooks * cfg.vocab_size,
            spec_for(cfg, "codebook_head"))
    elif not cfg.tie_embeddings:
        params["lm_head"] = rebranch.init_linear(
            keys[-1], cfg.d_model, cfg.vocab_size, spec_for(cfg, "lm_head"))
    return params


def _embed_inputs(params, batch, cfg: ArchConfig):
    """tokens [B,S] (or [B,S,Q] for multi-codebook) and/or precomputed
    frontend embeddings [B,S,d] (vision/audio stub)."""
    if "embeds" in batch:                  # modality stub path
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        if "tokens" in batch:
            x = x + _token_embed(params, batch["tokens"], cfg)
        return x
    return _token_embed(params, batch["tokens"], cfg)


def _token_embed(params, tokens, cfg: ArchConfig):
    if cfg.num_codebooks and tokens.ndim == 3:   # [B, S, Q] codebooks
        embs = layers.apply_embedding(
            params["embed"],
            tokens[..., 0] + 0, cfg)
        for q in range(1, cfg.num_codebooks):
            embs = embs + layers.apply_embedding(
                params["embed"], tokens[..., q], cfg)
        return embs
    return layers.apply_embedding(params["embed"], tokens, cfg)


def apply_head(params, x, cfg: ArchConfig):
    """ln_f + readout projection on [..., d] -> [..., V] / [..., Q, V],
    under the named scope ``lm_head``."""
    with jax.named_scope("lm_head"):
        x = layers.apply_rmsnorm(params["ln_f"], x, cfg.norm_eps)
        if cfg.num_codebooks:
            logits = rebranch.apply_linear(params["codebook_head"], x,
                                           spec_for(cfg, "codebook_head"))
            return logits.reshape(*logits.shape[:-1], cfg.num_codebooks,
                                  cfg.vocab_size)
        if cfg.tie_embeddings:
            return layers.embedding_as_logits(params["embed"], x, cfg)
        return rebranch.apply_linear(params["lm_head"], x,
                                     spec_for(cfg, "lm_head"))


def _readout(params, x, cfg: ArchConfig):
    return shard(apply_head(params, x, cfg), "batch", "seq", "vocab")


def features(params, batch, cfg: ArchConfig):
    """Forward through the blocks only (pre-ln_f hidden states)."""
    x = _embed_inputs(params, batch, cfg)
    x = shard(x, "batch", "seq_sp", "embed")
    positions = batch.get("positions")
    if cfg.mla:
        return _mla_layers(params, x, cfg, positions)[0]
    if cfg.scan_layers:
        def body(xx, block):
            out = _block_apply(block, xx, cfg, 0, positions=positions)[0]
            return shard(out, "batch", "seq_sp", "embed"), None
        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
        return x
    for i, block in enumerate(params["layers"]):
        fn = lambda p, xx, pos, _i=i: _block_apply(p, xx, cfg, _i,
                                                   positions=pos)[0]
        if cfg.remat:
            fn = jax.checkpoint(fn)
        x = shard(fn(block, x, positions), "batch", "seq_sp", "embed")
    return x


def forward(params, batch, cfg: ArchConfig):
    """Full-sequence forward (training).  cfg.remat checkpoints each block
    so train-step live memory is one residual stream per layer boundary."""
    return _readout(params, features(params, batch, cfg), cfg)


def _counters(cfg: ArchConfig) -> dict:
    """The on-device counters a latent-attention MoE cache carries:
    ``held_tokens`` [MoE layers, held experts], the token assignments
    each held expert computed in decode steps (of live rows in a paged
    pool, whose free rows read the trash block; of every row in a dense
    cache)."""
    if cfg.family != "moe":
        return {}
    lo, hi = cfg.held_range
    return {"counters": {"held_tokens": jnp.zeros(
        (cfg.num_layers - cfg.first_k_dense, hi - lo), jnp.int32)}}


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    if cfg.mla:
        return {"layers": mla.init_cache(cfg, batch, max_len, dtype),
                **_counters(cfg)}
    if cfg.scan_layers:   # stacked: leading L dim on every cache leaf
        one = layers.init_attention_cache(cfg, batch, max_len, 0, dtype)
        return {"layers": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers, *a.shape)),
            one)}
    return {
        "layers": [layers.init_attention_cache(cfg, batch, max_len, i, dtype)
                   for i in range(cfg.num_layers)],
    }


def init_paged_cache(cfg: ArchConfig, rows: int, n_blocks: int,
                     block_size: int, max_len: int, dtype=jnp.bfloat16):
    """Paged KV cache: shared physical blocks + per-row block tables.

    Same tree shape as :func:`init_cache` (one dict per layer, stacked
    under ``scan_layers``) but each layer carries ``n_blocks`` physical
    [block_size, KV, Dh] blocks plus a [rows, max_len/block_size] block
    table instead of dense [rows, max_len] KV rows.  Block tables are
    owned by :class:`repro.serve.pool.PagedPool`.  Latent attention
    pages its one stacked ``latent`` leaf the same way.
    """
    if cfg.mla:
        return {"layers": mla.init_paged_cache(cfg, rows, n_blocks,
                                               block_size, max_len, dtype),
                **_counters(cfg)}
    if cfg.scan_layers:
        one = layers.init_paged_attention_cache(
            cfg, rows, n_blocks, block_size, max_len, dtype)
        return {"layers": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers, *a.shape)),
            one)}
    return {
        "layers": [layers.init_paged_attention_cache(
            cfg, rows, n_blocks, block_size, max_len, dtype)
            for _ in range(cfg.num_layers)],
    }


def prefill(params, batch, cfg: ArchConfig, cache):
    x = _embed_inputs(params, batch, cfg)
    x = shard(x, "batch", "seq_sp", "embed")
    positions = batch.get("positions")
    if cfg.mla:
        x, cache = _mla_layers(params, x, cfg, positions, cache)
        return _readout(params, x[:, -1:, :], cfg), cache
    if cfg.scan_layers:
        def body(xx, inp):
            block, lc = inp
            out, nc = _block_apply(block, xx, cfg, 0, positions=positions,
                                   cache=lc)
            return shard(out, "batch", "seq_sp", "embed"), nc
        x, new_caches = jax.lax.scan(body, x,
                                     (params["layers"], cache["layers"]))
        logits = _readout(params, x[:, -1:, :], cfg)
        return logits, {"layers": new_caches}
    new_layer_caches = []
    for i, block in enumerate(params["layers"]):
        x, lc = _block_apply(block, x, cfg, i, positions=positions,
                             cache=cache["layers"][i])
        new_layer_caches.append(lc)
    logits = _readout(params, x[:, -1:, :], cfg)
    return logits, {"layers": new_layer_caches}


def decode_step(params, tokens, cfg: ArchConfig, cache):
    """One token per sequence against the KV cache. tokens: [B,1] (or
    [B,1,Q] multi-codebook)."""
    x = _token_embed(params, tokens, cfg)
    x = shard(x, "batch", None, "embed")
    if cfg.mla:
        x, cache = _mla_layers(params, x, cfg, None, cache, decode=True)
        return _readout(params, x, cfg), cache
    if cfg.scan_layers:
        def body(xx, inp):
            block, lc = inp
            out, nc = _block_apply(block, xx, cfg, 0, cache=lc, decode=True)
            return out, nc
        x, new_caches = jax.lax.scan(body, x,
                                     (params["layers"], cache["layers"]))
        return _readout(params, x, cfg), {"layers": new_caches}
    new_layer_caches = []
    for i, block in enumerate(params["layers"]):
        x, lc = _block_apply(block, x, cfg, i,
                             cache=cache["layers"][i], decode=True)
        new_layer_caches.append(lc)
    logits = _readout(params, x, cfg)
    return logits, {"layers": new_layer_caches}


def _mla_layers(params, x, cfg: ArchConfig, positions, cache=None,
                decode: bool = False):
    """Every layer of a latent-attention model, the leading dense ones
    first, then the scanned MoE stack, each block through
    :func:`_residual`: (x, cache).

    The stacked ``latent`` cache leaf and the held-expert counters ride
    the scans' carry, and each layer writes its own slice of the leaf.
    The per-layer slices as the scans' inputs and outputs, as the other
    attention's cache is threaded, would not fit: compiled for a v5e,
    DeepSeek-V2-Lite's decode step at 32 rows x 1536 (a 3.06 GB float32
    cache) needs 7.99 GB of temporaries that way against 0.87 GB in the
    carry.  Without a cache (the training forward) each layer attends
    over the call's tokens alone."""
    b, s, _ = x.shape
    lc = None if cache is None else cache["layers"]
    length = None if lc is None else lc["length"][0]            # [B]
    table = None if lc is None else lc.get("table")
    table = None if table is None else table[0]
    if positions is None:
        positions = (jnp.broadcast_to(jnp.arange(s)[None], (b, s))
                     if lc is None else length[:, None] + jnp.arange(s))
    counts = None if cache is None else cache.get("counters")
    counts = None if counts is None else counts["held_tokens"]
    live = None
    if decode and table is not None:     # free rows read the trash block
        live = table[:, 0] != lc["latent"].shape[1] - 1
    attn_cfg = site_cfg(cfg, "blocks.attn")
    nd = cfg.first_k_dense

    def body(carry, inp):
        xx, kv, cnt = carry
        block, i = inp

        def attend(p, h):
            return mla.apply(p, h, attn_cfg, positions, kv, i, table,
                             length, decode)
        xx, kv, got = _residual(block, xx, cfg, attend,
                                serving=cache is not None, live=live)
        if decode and got is not None and cnt is not None:
            cnt = cnt.at[i - nd].add(got)
        return (shard(xx, "batch", "seq_sp", "embed"), kv, cnt), None

    if cfg.remat and cache is None:
        body = jax.checkpoint(body)
    carry = (x, None if lc is None else lc["latent"], counts)
    stacks = ([(params["dense_layers"], 0)] if nd else []) \
        + [(params["layers"], nd)]
    for stack, first in stacks:
        n = jax.tree.leaves(stack)[0].shape[0]
        carry, _ = jax.lax.scan(body, carry,
                                (stack, first + jnp.arange(n)))
    x, kv, counts = carry
    if cache is None:
        return x, None
    out = {**cache, "layers": {**lc, "latent": kv,
                               "length": lc["length"] + s}}
    if counts is not None:
        out["counters"] = {"held_tokens": counts}
    return x, out


def verify_step(params, tokens, cfg: ArchConfig, cache):
    """Speculative VERIFY: a k-token block per sequence in one pass.

    tokens: [B, k] — per row, the last accepted token followed by the
    first k-1 drafted tokens.  Returns logits [B, k, V]: position i's
    argmax is the TRUE next token after input i (the decode path writes
    each token's KV before attending, with per-query validity masks), so
    the caller accepts the longest drafted prefix that matches and takes
    the first mismatch's correction for free — bit-identical to k plain
    ``decode_step`` calls on the accepted prefix.  The cache comes back
    advanced by k on every row; the serving pool rolls rejected tail
    entries back (``rollback``).  The model body IS ``decode_step`` —
    every layer is seq-width generic; only the deploy-surface geometry
    check distinguishes the two.
    """
    return decode_step(params, tokens, cfg, cache)
