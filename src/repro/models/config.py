"""Architecture configuration for every supported model family."""

from __future__ import annotations

import dataclasses
from typing import Any

from repro.core.rebranch import ReBranchSpec


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN rotary scaling (arXiv:2309.00071), as DeepSeek-V2's
    ``rope_scaling`` gives it."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 32000
    mlp_type: str = "swiglu"       # swiglu | geglu | gelu
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    mrope: bool = False            # qwen2-vl M-RoPE (3-section rotary)
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # --- MoE ---
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0
    moe_group_size: int = 1024     # dispatch group (memory/locality knob)
    moe_capacity_factor: float = 1.25
    moe_norm_topk: bool = True     # renormalise the top-k gates to sum 1
    shared_expert_gate: bool = True   # sigmoid gate on the shared experts
    # (lo, hi): the contiguous range of routed experts this chip holds
    # (expert parallelism); () holds all of them
    experts_held: tuple = ()
    first_k_dense: int = 0         # leading layers with a dense MLP (d_ff)
    # --- multi-head latent attention (DeepSeek-V2); 0 -> off ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: YaRN | None = None
    # --- SSM (mamba-1) ---
    ssm_state: int = 0
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0               # 0 -> ceil(d_model / 16)
    ssm_norm: bool = False         # falcon-mamba: RMSNorm on dt/B/C
    # --- hybrid (hymba) ---
    sliding_window: int = 0        # 0 -> full attention everywhere
    full_attn_layers: tuple = ()   # layer idxs with global attention
    # --- multi-codebook audio (musicgen) ---
    num_codebooks: int = 0
    # --- frontend stub ---
    frontend: str = "none"         # none | vision | audio
    # --- technique ---
    rebranch: ReBranchSpec = dataclasses.field(default_factory=ReBranchSpec)
    # Per-site mapping overrides: ((address, ReBranchSpec), ...) resolved
    # by spec_for() with longest-prefix matching.  Addresses live in the
    # family's enumerated site tree (repro.plan.sites): leaf sites like
    # 'blocks.attn' / 'blocks.ssm.in_proj' / 'lm_head' or ancestor
    # prefixes like 'blocks', so e.g. the readout can stay SRAM-trainable
    # while the trunk is ROM, or one component can run another engine —
    # the paper's Fig. 12 per-layer ROM/SRAM area map.  Normally built by
    # repro.deploy.compile_model from a repro.plan.PlacementPlan.
    rebranch_overrides: tuple = ()
    # --- numerics ---
    dtype: Any = "bfloat16"
    remat: bool = True             # per-block activation checkpointing (train)
    # --- attention chunking (memory-bounded attention) ---
    attn_chunk: int = 1024

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.num_heads)
        if self.dt_rank == 0 and self.ssm_state:
            object.__setattr__(self, "dt_rank", -(-self.d_model // 16))
        if self.first_k_dense and not self.kv_lora_rank:
            raise ValueError(
                f"{self.name!r}: leading dense layers (first_k_dense) "
                f"run only with the latent-attention cache")
        if self.experts_held:
            lo, hi = self.experts_held
            if not 0 <= lo < hi <= self.num_experts:
                raise ValueError(
                    f"{self.name!r}: experts_held {self.experts_held} is "
                    f"not a range inside [0, {self.num_experts})")

    @property
    def held_range(self) -> tuple[int, int]:
        """(lo, hi) of the routed experts this chip holds."""
        return self.experts_held or (0, self.num_experts)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def scan_layers(self) -> bool:
        """Stacked-params lax.scan over layers (compile time O(1) in L).
        Hybrid archs keep a python loop: per-layer SWA window / cache
        shapes are heterogeneous."""
        return self.family != "hybrid"

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic sequence mixing (long_500k eligibility)."""
        return self.family in ("ssm", "hybrid")

    def uses_full_attention(self, layer_idx: int) -> bool:
        if self.sliding_window == 0:
            return True
        return layer_idx in self.full_attn_layers


def spec_for(cfg, site: str) -> ReBranchSpec:
    """The ReBranchSpec governing one named parameter group (``site``).

    Works for any config carrying ``rebranch`` + ``rebranch_overrides``
    (ArchConfig and models.cnn.CNNConfig).  Sites are dotted paths in the
    family's site tree (see ``repro.plan.sites``); an override addresses
    either a leaf site exactly or an ancestor prefix (``'blocks'`` governs
    ``'blocks.attn'``, ``'blocks.mlp'``, ...).  The LONGEST matching
    override wins; unoverridden sites fall back to the config-wide spec.

    Validation happens where the enumerated site tree is known —
    ``repro.plan.PlacementPlan`` / ``repro.deploy.compile_model`` reject
    addresses outside the tree; this lookup stays a thin trace-time
    resolver.
    """
    return resolve_override(getattr(cfg, "rebranch_overrides", ()),
                            site, cfg.rebranch)


def resolve_override(entries, site: str, default):
    """Longest-prefix resolution over ((address, spec), ...) entries.

    THE one resolver — ``spec_for`` (trace time) and
    ``repro.plan.PlacementPlan.spec`` (plan time) both call it, so a
    plan can never disagree with what the model actually traces.
    """
    best, best_len = None, -1
    for s, spec in entries:
        if (s == site or site.startswith(s + ".")) and len(s) > best_len:
            best, best_len = spec, len(s)
    return default if best is None else best

