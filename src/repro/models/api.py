"""Family-dispatched model API: one interface for every architecture.

  init(key, cfg)                          -> params
  forward(params, batch, cfg)             -> logits          (train)
  prefill(params, batch, cfg, cache)      -> (logits, cache) (serve)
  decode_step(params, tokens, cfg, cache) -> (logits, cache) (serve)
  init_cache(cfg, batch, max_len)         -> cache

DEPRECATED as a user entrypoint: prefer ``repro.deploy.compile_model``,
which resolves the TrunkEngine and the per-layer ROM/SRAM mapping once
and returns these same functions bound to the resolved config.  The free
functions stay as thin shims (deploy and the remaining callers route
through them) and behave identically for configs without overrides.
"""

from __future__ import annotations

from repro.models import hybrid, ssm, transformer
from repro.models.config import ArchConfig

_FAMILY = {
    "dense": transformer, "vlm": transformer, "audio": transformer,
    "moe": transformer,            # moe block dispatched inside transformer
    "ssm": ssm,
    "hybrid": hybrid,
}


def _mod(cfg: ArchConfig):
    return _FAMILY[cfg.family]


def init(key, cfg: ArchConfig):
    return _mod(cfg).init(key, cfg)


def forward(params, batch, cfg: ArchConfig):
    return _mod(cfg).forward(params, batch, cfg)


def features(params, batch, cfg: ArchConfig):
    return _mod(cfg).features(params, batch, cfg)


def apply_head(params, x, cfg: ArchConfig):
    return _mod(cfg).apply_head(params, x, cfg)


def prefill(params, batch, cfg: ArchConfig, cache):
    return _mod(cfg).prefill(params, batch, cfg, cache)


def decode_step(params, tokens, cfg: ArchConfig, cache):
    return _mod(cfg).decode_step(params, tokens, cfg, cache)


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None):
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    return _mod(cfg).init_cache(cfg, batch, max_len, dtype)


def supports_paging(cfg: ArchConfig) -> bool:
    """Whether the family can serve decode through a paged KV cache.

    Paging indirects KV rows through block tables, which requires every
    sequence-mixing layer to keep an attention cache with one uniform
    full-attention horizon: ssm state is O(1) (nothing to page), hybrid
    mixes ssm state with per-layer SWA windows, and SWA rings smaller
    than ``max_len`` cannot share one block table.
    """
    return (_mod(cfg) is transformer and cfg.sliding_window == 0)


def supports_speculation(cfg: ArchConfig) -> bool:
    """Whether the family can serve speculative (draft/verify) decode.

    Verify writes k KV entries per row and must be able to UNDO the
    rejected tail by truncating the row's length: that requires every
    sequence-mixing layer to keep a full-horizon attention cache.  SWA
    rings can wrap within a k-block (the overwritten entry is
    unrecoverable) and ssm/hybrid recurrent state cannot rewind at all.
    The predicate is :func:`supports_paging` (for the same structural
    reason: a uniform full-attention horizon) less latent attention,
    whose decode takes one token per row (no k-token verify).
    """
    return (_mod(cfg) is transformer and cfg.sliding_window == 0
            and not cfg.mla)


def verify_step(params, tokens, cfg: ArchConfig, cache):
    """Speculative verify: k-token block decode (see
    ``transformer.verify_step``).  Raises for families that cannot
    speculate (:func:`supports_speculation`)."""
    if not supports_speculation(cfg):
        raise ValueError(
            f"{cfg.name!r} (family {cfg.family!r}, sliding_window="
            f"{cfg.sliding_window}, kv_lora_rank={cfg.kv_lora_rank}) "
            f"cannot run speculative verify: rolling back rejected "
            f"drafts needs a full-horizon attention cache (ssm/hybrid "
            f"recurrent state cannot rewind; SWA rings overwrite "
            f"entries a rollback would need), and latent attention "
            f"has no k-token verify")
    return _mod(cfg).verify_step(params, tokens, cfg, cache)


def draft_config(cfg: ArchConfig) -> ArchConfig:
    """The branch-only DRAFT variant of ``cfg`` for speculative decode.

    Every ReBranch-enabled site gets ``trunk_skip=True``: its ROM trunk
    matmul is skipped and only the SRAM branch runs (~1/compression of
    the FLOPs, see ``core.rebranch``).  SRAM-resident sites
    (``enabled=False`` under the PlacementPlan's residency map) are
    plain trainable linears and run in full — they are the cheap part by
    placement.  The draft model shares the verify model's params tree
    verbatim (``trunk_skip`` is control flow, not weights), so a draft
    forward needs no extra memory and scenario hot-swaps apply to both
    at once.
    """
    import dataclasses

    def skip(spec):
        if not spec.enabled or spec.trunk_skip:
            return spec
        return dataclasses.replace(spec, trunk_skip=True)

    return dataclasses.replace(
        cfg, rebranch=skip(cfg.rebranch),
        rebranch_overrides=tuple(
            (site, skip(spec))
            for site, spec in getattr(cfg, "rebranch_overrides", ())))


def supports_chunked_prefill(cfg: ArchConfig) -> bool:
    """Whether prefill may be split into chunks across an existing cache.

    Chunked prefill replays the prompt through ``prefill`` with the
    partially-filled cache and explicit positions; the attention layers
    then attend over the cached prefix.  That works for every family
    whose sequence mixing is attention-with-KV-cache (the transformer
    module).  SSM layers carry recurrent + conv state that ``prefill``
    rebuilds from position 0 each call, so ssm/hybrid prompts must
    prefill whole.
    """
    return _mod(cfg) is transformer


def init_paged_cache(cfg: ArchConfig, rows: int, n_blocks: int,
                     block_size: int, max_len: int, dtype=None):
    """Paged KV cache (see ``transformer.init_paged_cache``).

    Raises for families that cannot page (:func:`supports_paging`).
    """
    import jax.numpy as jnp
    if not supports_paging(cfg):
        raise ValueError(
            f"{cfg.name!r} (family {cfg.family!r}, sliding_window="
            f"{cfg.sliding_window}) cannot serve through a paged KV "
            f"cache; use init_cache + a dense SlotPool")
    dtype = dtype or jnp.bfloat16
    return _mod(cfg).init_paged_cache(cfg, rows, n_blocks, block_size,
                                      max_len, dtype)


def cache_geometry(cfg: ArchConfig, cache) -> tuple[int, int | None]:
    """(batch, horizon) a serve cache was built for.

    Works on the cache TREE (shapes only, jit-tracer safe).  Every cache
    leaf carries batch at axis 0 — axis 1 under scan-stacked layers,
    where leaves gain a leading L dim.  The horizon is the largest K/V
    sequence axis across layers (full-attention layers hold ``max_len``;
    SWA layers only their window); ``None`` for attention-free (O(1)
    state) families, whose horizon is unbounded.

    Paged caches (leaves carrying a ``table`` entry, see
    :func:`init_paged_cache`) report their LOGICAL geometry: batch is
    the block-table row count and the horizon is
    ``table_width * block_size`` — what the gathered attention view
    holds, not the physical block count.
    """
    import jax
    axis = 1 if cfg.scan_layers else 0
    first = _first_layer(cache)
    if isinstance(first, dict) and "table" in first:
        table = first["table"]                          # [(L,) B, NB]
        blocks = first["latent" if "latent" in first else "k"]
        return table.shape[axis], table.shape[-1] * blocks.shape[axis + 1]
    if isinstance(first, dict) and "latent" in first:   # [L, B, S, W]
        return first["latent"].shape[axis], first["latent"].shape[axis + 1]
    leaves = jax.tree.leaves(cache)
    if not leaves:
        raise ValueError("empty cache tree")
    batch = leaves[0].shape[axis]
    if cfg.is_attention_free:
        return batch, None
    # K/V leaves are [(L,) B, S, KV, Dh] — the only rank-(4+axis) leaves
    # (ssm state inside hybrids is rank 3, lengths rank 1+axis)
    kv = [leaf.shape[1 + axis] for leaf in leaves
          if leaf.ndim == 4 + axis]
    return batch, max(kv)


def _first_layer(cache):
    """The first per-layer cache dict (the stacked dict under scan)."""
    if not isinstance(cache, dict):
        return None
    layers = cache.get("layers")
    if isinstance(layers, (list, tuple)):
        return layers[0] if layers else None
    return layers
