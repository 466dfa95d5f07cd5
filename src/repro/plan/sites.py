"""The site protocol: every model family's enumerable parameter-group tree.

A *site* is a named group of trunk weights that one ``ReBranchSpec``
governs — the unit at which the paper maps layers onto ROM-CiM vs
SRAM-CiM (Fig. 12).  Site names are dotted paths; model code resolves
them at trace time through ``models.config.spec_for`` (longest-prefix
match, so an override at ``'blocks'`` governs ``'blocks.attn'``,
``'blocks.ssm.in_proj'``, ...).

This module is the ONE enumeration the rest of the system validates
against: :class:`repro.plan.PlacementPlan` and
``repro.deploy.compile_model`` reject addresses outside
:func:`valid_addresses`, and the cost-driven planner (``plan.solve``)
prices each site from the shapes/MAC counts recorded here.

Site trees per family (leaf sites; ancestors are valid override
addresses too):

  transformer (dense/vlm/audio) : blocks.attn, blocks.mlp, lm_head |
                                  codebook_head (untied readouts only)
  moe                           : blocks.attn, blocks.moe, lm_head;
                                  blocks.mlp too with leading dense
                                  layers (first_k_dense)
  ssm (mamba)                   : blocks.{in,x,dt,out}_proj, lm_head
  hybrid (hymba)                : blocks.attn, blocks.ssm.{...}_proj,
                                  blocks.mlp, lm_head
  cnn (vgg8/resnet18/darknet19/tiny_yolo): the conv sites enumerated by
      ``models.cnn.conv_site_shapes`` ('stem', 'convs.N',
      'stages.S.B.convK', 'head.N')

Small always-SRAM parameters (norms, biases, routers, BN, the YOLO 1x1
predictor) and the always-ROM embedding table are not sites: they never
move between substrates.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Site:
    """One named trunk parameter group of a model's site tree.

    weights : trunk weight scalars per occurrence.
    macs    : trunk MACs per unit of work per occurrence — per TOKEN for
              LM families, per INFERENCE for CNNs (matching what the
              Fig. 12-14 cost model consumes for each).
    count   : identical occurrences sharing this site (scan-stacked
              layers); totals multiply it in.
    shape   : representative weight shape — (d_in, d_out) for matmul
              sites, (k, k, c_in, c_out) for convs; composite sites
              (several projections under one name) record their members
              in ``members`` instead.
    """
    name: str
    kind: str                       # 'matmul' | 'conv'
    weights: int
    macs: int
    count: int = 1
    shape: tuple = ()
    members: tuple = ()             # ((label, (d_in, d_out)), ...)
    # ReBranch accounting members: ((d_in, d_out, core_rep, core_active),
    # ...) per occurrence — core_rep replicas of the trainable core share
    # ONE fixed C/U pair (stacked MoE experts: rep=E), of which
    # core_active run per unit of work (top-k routing).  None -> derived
    # from ``members``/``shape`` with rep = active = 1.
    branch_members: tuple | None = None

    @property
    def total_weights(self) -> int:
        return self.weights * self.count

    @property
    def total_macs(self) -> int:
        return self.macs * self.count

    def branch_costs(self, spec) -> tuple:
        """(rom_proj_weights, core_weights, branch_macs) per occurrence —
        the ONE home of ReBranch cost accounting (PlacementPlan.stats and
        the solver's area pricing both consume it).  Mirrors
        core.rebranch.init_linear / models.cnn.init_conv /
        models.moe.init_expert_linear: C/U projections are fixed (ROM),
        the core is the trainable SRAM tensor.  branch_macs are per the
        site's MAC unit (token / inference)."""
        if self.kind == "conv":
            k, _, c_in, c_out = self.shape
            c_c = max(1, c_in // spec.d_ratio)
            c_u = max(1, c_out // spec.u_ratio)
            reuse = self.macs / max(1, self.weights)   # spatial positions
            proj = c_in * c_c + c_u * c_out
            core = k * k * c_c * c_u
            return proj, core, int((proj + k * k * c_c * c_u) * reuse)
        bm = self.branch_members
        if bm is None:
            bm = tuple((a, b, 1, 1)
                       for _, (a, b) in (self.members or
                                         (("w", self.shape),)))
        proj = core = bmacs = 0
        for d_in, d_out, rep, active in bm:
            d_c = max(1, d_in // spec.d_ratio)
            d_u = max(1, d_out // spec.u_ratio)
            proj += d_in * d_c + d_u * d_out
            core += d_c * d_u * rep
            bmacs += (d_in * d_c + d_c * d_u + d_u * d_out) * active
        return proj, core, bmacs


def _matmul_site(name: str, members, count: int = 1) -> Site:
    """Composite matmul site: members are (label, (d_in, d_out)) pairs.
    Matmul MACs per token = weight count (one MAC per weight)."""
    members = tuple((lbl, tuple(shape)) for lbl, shape in members)
    w = sum(a * b for _, (a, b) in members)
    single = members[0][1] if len(members) == 1 else ()
    return Site(name=name, kind="matmul", weights=w, macs=w, count=count,
                shape=single, members=members)


# ---------------------------------------------------------------------------
# per-family site trees
# ---------------------------------------------------------------------------

def _attn_members(cfg):
    d, h, kv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if cfg.mla:                  # latent attention (models.mla)
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        r = cfg.kv_lora_rank
        return [("q_proj", (d, h * (dn + dr))),
                ("kv_a_proj_with_mqa", (d, r + dr)),
                ("kv_b_proj", (r, h * (dn + dv))),
                ("o_proj", (h * dv, d))]
    return [("q", (d, h * dh)), ("k", (d, kv * dh)),
            ("v", (d, kv * dh)), ("o", (h * dh, d))]


def _mlp_members(cfg, d_ff=None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_type in ("swiglu", "geglu"):
        return [("gate", (d, ff)), ("up", (d, ff)), ("down", (ff, d))]
    return [("up", (d, ff)), ("down", (ff, d))]


def _head_sites(cfg):
    if cfg.num_codebooks:
        return [_matmul_site("codebook_head",
                             [("w", (cfg.d_model,
                                     cfg.num_codebooks * cfg.vocab_size))])]
    if cfg.tie_embeddings:
        return []                   # readout reuses the ROM embedding table
    return [_matmul_site("lm_head", [("w", (cfg.d_model, cfg.vocab_size))])]


def _moe_site(cfg) -> Site:
    """Stacked ReBranch experts: weights cover the E experts held here
    (``cfg.experts_held``; all by default); MACs per token only the
    top-k active ones, at most E (plus the always-on shared expert).
    The experts share ONE C/U sketch pair per stack with a per-expert
    core (models.moe.init_expert_linear), recorded in branch_members as
    (d_in, d_out, rep=E, active=k).  Leading dense layers are not MoE
    layers: the site counts the others."""
    lo, hi = cfg.held_range
    d, ff, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, hi - lo
    k = min(cfg.num_experts_per_tok, e)
    members = [("gate", (d, ff)), ("up", (d, ff)), ("down", (ff, d))]
    w_expert = sum(a * b for _, (a, b) in members)
    weights, macs = e * w_expert, k * w_expert
    all_members = [(f"experts.{lbl}", (e * a, b)) for lbl, (a, b) in members]
    branch = [(a, b, e, k) for _, (a, b) in members]
    if cfg.num_shared_experts:
        shared_ff = cfg.num_shared_experts * ff
        shared = _mlp_members(cfg, d_ff=shared_ff)
        w_shared = sum(a * b for _, (a, b) in shared)
        weights += w_shared
        macs += w_shared
        all_members += [(f"shared.{lbl}", shape) for lbl, shape in shared]
        branch += [(a, b, 1, 1) for _, (a, b) in shared]
    return Site(name="blocks.moe", kind="matmul", weights=weights,
                macs=macs, count=cfg.num_layers - cfg.first_k_dense,
                members=tuple((lbl, tuple(s)) for lbl, s in all_members),
                branch_members=tuple(branch))


def _ssm_proj_sites(cfg, prefix: str) -> list:
    d, di, n, dtr = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    return [
        _matmul_site(f"{prefix}.in_proj", [("w", (d, 2 * di))],
                     count=cfg.num_layers),
        _matmul_site(f"{prefix}.x_proj", [("w", (di, dtr + 2 * n))],
                     count=cfg.num_layers),
        _matmul_site(f"{prefix}.dt_proj", [("w", (dtr, di))],
                     count=cfg.num_layers),
        _matmul_site(f"{prefix}.out_proj", [("w", (di, d))],
                     count=cfg.num_layers),
    ]


def _arch_sites(cfg) -> list:
    fam = cfg.family
    if fam in ("dense", "vlm", "audio"):
        return [_matmul_site("blocks.attn", _attn_members(cfg),
                             count=cfg.num_layers),
                _matmul_site("blocks.mlp", _mlp_members(cfg),
                             count=cfg.num_layers)] + _head_sites(cfg)
    if fam == "moe":
        dense = ([_matmul_site("blocks.mlp", _mlp_members(cfg),
                               count=cfg.first_k_dense)]
                 if cfg.first_k_dense else [])
        return [_matmul_site("blocks.attn", _attn_members(cfg),
                             count=cfg.num_layers)] + dense \
            + [_moe_site(cfg)] + _head_sites(cfg)
    # ssm/hybrid init always build a real lm_head ReBranch group (their
    # families ignore tie_embeddings/num_codebooks), so the site is
    # unconditional — _head_sites applies transformer-family rules only
    lm_head = _matmul_site("lm_head", [("w", (cfg.d_model,
                                              cfg.vocab_size))])
    if fam == "ssm":
        return _ssm_proj_sites(cfg, "blocks") + [lm_head]
    if fam == "hybrid":
        return ([_matmul_site("blocks.attn", _attn_members(cfg),
                              count=cfg.num_layers)]
                + _ssm_proj_sites(cfg, "blocks.ssm")
                + [_matmul_site("blocks.mlp", _mlp_members(cfg),
                                count=cfg.num_layers)]
                + [lm_head])
    raise ValueError(f"no site tree for model family {fam!r}")


def _cnn_sites(cfg) -> list | None:
    from repro.models import cnn
    shapes = cnn.conv_site_shapes(cfg)
    if shapes is None:
        return None
    return [Site(name=site, kind="conv", weights=k * k * c_in * c_out,
                 macs=hw * hw * k * k * c_in * c_out,
                 shape=(k, k, c_in, c_out))
            for site, k, c_in, c_out, hw, _stride in shapes]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def site_tree(cfg) -> tuple:
    """The enumerated, ordered site tree of ``cfg`` (tuple of Site).

    Raises for configs whose sites cannot be enumerated (unknown family,
    or a CNN name registered outside models.cnn.MODEL_REGISTRY) — use
    :func:`try_site_tree` when unconstrained configs are acceptable.
    """
    from repro.models import cnn
    if isinstance(cfg, cnn.CNNConfig):
        sites = _cnn_sites(cfg)
        if sites is None:
            raise ValueError(
                f"cannot enumerate sites for CNN {cfg.name!r}: not in "
                f"models.cnn.MODEL_REGISTRY")
        tree = tuple(sites)
    else:
        tree = tuple(_arch_sites(cfg))
    names = [s.name for s in tree]
    dup = {n for n in names if names.count(n) > 1}
    if dup:                         # a builder bug, catch it loudly
        raise ValueError(f"duplicate sites in {cfg.name!r} tree: "
                         f"{sorted(dup)}")
    return tree


def try_site_tree(cfg):
    """site_tree, or None when the config's sites cannot be enumerated."""
    try:
        return site_tree(cfg)
    except ValueError:
        return None


def valid_addresses(tree) -> set:
    """Every address an override may use: leaf site names plus all their
    dotted ancestor prefixes ('blocks' governs every 'blocks.*' site,
    'stages.1' a whole ResNet stage)."""
    out = set()
    for site in tree:
        parts = site.name.split(".")
        for i in range(1, len(parts) + 1):
            out.add(".".join(parts[:i]))
    return out


def sites_under(tree, address: str) -> tuple:
    """The leaf sites an override address governs (exact or prefix)."""
    return tuple(s for s in tree
                 if s.name == address or s.name.startswith(address + "."))
