"""The PlacementPlan subsystem: site trees, plans, solver, deploy parity.

Covers the placement contract:
  * site enumeration — every ReBranch-capable parameter group of each
    family config maps to exactly one leaf site (parametrized over
    transformer / cnn / ssm / hybrid / moe), and the site tree's weight
    counts match the actual initialised parameters;
  * PlacementPlan — round-trip through rebranch_overrides, longest-prefix
    resolution, unknown / duplicate sites raise;
  * plan.solve — Fig. 12 qualitative shape on DarkNet-19 (small early /
    late layers flip to SRAM first, bulk mid convs stay ROM), budget
    monotonicity, stats/area bookkeeping;
  * deploy.compile_model(cfg, plan=...) — bit-identical to the
    equivalent hand-written rebranch_overrides deployment for all three
    builtin engines.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import deploy, plan
from repro.models import api, cnn
from repro.models.config import ArchConfig, spec_for

ENGINES = ["int8_native", "dequant", "pallas"]


def _lm_cfg(**kw):
    base = dict(name="t_plan", family="dense", num_layers=2, d_model=32,
                num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=128,
                remat=False, dtype="float32")
    base.update(kw)
    return ArchConfig(**base)


FAMILY_CFGS = {
    "transformer": _lm_cfg(),
    "moe": _lm_cfg(name="m_plan", family="moe", num_experts=4,
                   num_experts_per_tok=2, moe_d_ff=32,
                   num_shared_experts=1),
    # latent attention, a leading dense layer, 3 of 6 experts held
    "mla_moe": _lm_cfg(name="d_plan", family="moe", num_layers=3,
                       num_experts=6, num_experts_per_tok=2, moe_d_ff=32,
                       num_shared_experts=2, shared_expert_gate=False,
                       experts_held=(2, 5), first_k_dense=1,
                       kv_lora_rank=16, qk_nope_head_dim=8,
                       qk_rope_head_dim=4, v_head_dim=8),
    "ssm": _lm_cfg(name="s_plan", family="ssm", num_heads=0,
                   num_kv_heads=0, d_ff=0, ssm_state=4),
    "hybrid": _lm_cfg(name="h_plan", family="hybrid", ssm_state=4,
                      sliding_window=8, full_attn_layers=(0,)),
    "cnn": cnn.CNNConfig(name="vgg8", num_classes=13, input_size=16),
    "cnn_resnet": cnn.CNNConfig(name="resnet18", num_classes=13,
                                input_size=16),
    "cnn_darknet": cnn.CNNConfig(name="tiny_yolo", input_size=32),
}


def _init_params(cfg):
    if isinstance(cfg, cnn.CNNConfig):
        init_fn, _ = cnn.MODEL_REGISTRY[cfg.name]
        return jax.eval_shape(lambda k: init_fn(k, cfg),
                              jax.random.PRNGKey(0))
    return jax.eval_shape(lambda k: api.init(k, cfg), jax.random.PRNGKey(0))


def _rebranch_groups(params):
    """Paths of every ReBranch-capable parameter group: dict nodes holding
    a ROM trunk image ({'rom': {'w_q': ...}}) — exactly the groups a site
    governs.  Embedding tables (ROM but never remappable) are excluded."""
    out = []

    def walk(path, node):
        if isinstance(node, dict):
            if "rom" in node and isinstance(node["rom"], dict) \
                    and "w_q" in node["rom"]:
                out.append(path)
                return
            for k, v in node.items():
                walk(path + (k,), v)
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(path + (i,), v)

    walk((), params)
    return out


def _trunk_weights(params):
    """Total trunk (w_q) weight count over all ReBranch groups."""
    total = 0

    def walk(node):
        nonlocal total
        if isinstance(node, dict):
            if "rom" in node and isinstance(node["rom"], dict) \
                    and "w_q" in node["rom"]:
                total += int(np.prod(node["rom"]["w_q"].shape))
                return
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(params)
    return total


# ---------------------------------------------------------------------------
# site enumeration
# ---------------------------------------------------------------------------

class TestSiteTrees:
    @pytest.mark.parametrize("family", sorted(FAMILY_CFGS))
    def test_every_group_maps_to_exactly_one_site(self, family):
        """Each ReBranch parameter group resolves to exactly ONE leaf site
        — flipping that site (and only it) to SRAM removes the group's
        ROM image; every other group keeps its placement."""
        cfg = FAMILY_CFGS[family]
        tree = plan.site_tree(cfg)
        names = [s.name for s in tree]
        assert len(names) == len(set(names))          # leaves are unique
        # leaf sites never nest (a leaf being another leaf's prefix would
        # make resolution ambiguous)
        for a in names:
            for b in names:
                assert a == b or not b.startswith(a + "."), (a, b)
        groups = _rebranch_groups(_init_params(cfg))
        assert groups, family
        n_groups = len(groups)
        for site in tree:
            sram = dataclasses.replace(cfg.rebranch, enabled=False)
            cfg2 = dataclasses.replace(
                cfg, rebranch_overrides=((site.name, sram),))
            remaining = _rebranch_groups(_init_params(cfg2))
            # the site governs >= 1 group; every group it governed is gone
            # and none of the others moved
            assert len(remaining) < n_groups, site.name
            assert set(remaining) <= set(groups), site.name
        # all sites SRAM -> no ROM groups anywhere: the tree COVERS the
        # model (no group escapes the enumeration)
        all_sram = dataclasses.replace(cfg.rebranch, enabled=False)
        cfg3 = dataclasses.replace(
            cfg, rebranch_overrides=tuple((n, all_sram) for n in names))
        leftovers = _rebranch_groups(_init_params(cfg3))
        # embeddings are the one always-ROM non-site group in LM families
        assert all(p[0] == "embed" for p in leftovers), leftovers

    @pytest.mark.parametrize("family", sorted(FAMILY_CFGS))
    def test_site_weight_counts_match_params(self, family):
        """The tree's trunk weight totals equal the actually-initialised
        ROM image (the cost model prices real bits, not estimates)."""
        cfg = FAMILY_CFGS[family]
        tree = plan.site_tree(cfg)
        want = _trunk_weights(_init_params(cfg))
        got = sum(s.total_weights for s in tree)
        assert got == want, (family, got, want)

    def test_moe_branch_costs_match_real_shapes(self):
        """The MoE expert stacks share ONE C/U pair per stack with a
        per-expert core (models.moe.init_expert_linear) — branch pricing
        must match those actual array sizes, not densify C per expert."""
        cfg = FAMILY_CFGS["moe"]
        site = next(s for s in plan.site_tree(cfg)
                    if s.name == "blocks.moe")
        proj_w, core_w, bmacs = site.branch_costs(cfg.rebranch)
        params = _init_params(cfg)
        layers0 = params["layers"]                    # stacked (scan)
        want_proj = want_core = 0
        for blk in ("gate", "up", "down"):
            p = jax.tree.map(lambda a: a, layers0["moe"]["experts"][blk])
            # leading L dim from vmap-stacked init: strip it
            want_proj += (int(np.prod(p["rom"]["C"].shape[1:]))
                          + int(np.prod(p["rom"]["U"].shape[1:])))
            want_core += int(np.prod(p["sram"]["core"].shape[1:]))
        for blk in ("gate", "up", "down"):
            sh = layers0["moe"]["shared"][blk]
            want_proj += (int(np.prod(sh["rom"]["C"].shape[1:]))
                          + int(np.prod(sh["rom"]["U"].shape[1:])))
            want_core += int(np.prod(sh["sram"]["core"].shape[1:]))
        assert proj_w == want_proj, (proj_w, want_proj)
        assert core_w == want_core, (core_w, want_core)
        # branch MACs: top-k active experts + the always-on shared expert
        d, ff = cfg.d_model, cfg.moe_d_ff
        k, dr, ur = cfg.num_experts_per_tok, cfg.rebranch.d_ratio, \
            cfg.rebranch.u_ratio
        per = lambda a, b: (a * max(1, a // dr) + max(1, a // dr)
                            * max(1, b // ur) + max(1, b // ur) * b)
        sff = cfg.num_shared_experts * ff
        want = k * (2 * per(d, ff) + per(ff, d)) \
            + 2 * per(d, sff) + per(sff, d)
        assert bmacs == want, (bmacs, want)

    def test_ssm_head_site_unconditional(self):
        """ssm/hybrid init always build lm_head, even under
        tie_embeddings — the site tree must list it."""
        cfg = dataclasses.replace(FAMILY_CFGS["ssm"], tie_embeddings=True)
        names = {s.name for s in plan.site_tree(cfg)}
        assert "lm_head" in names

    def test_unknown_family_raises(self):
        cfg = dataclasses.replace(_lm_cfg(), family="novel")
        with pytest.raises(ValueError, match="novel"):
            plan.site_tree(cfg)
        assert plan.try_site_tree(cfg) is None

    def test_valid_addresses_include_prefixes(self):
        tree = plan.site_tree(FAMILY_CFGS["hybrid"])
        addrs = plan.valid_addresses(tree)
        assert {"blocks", "blocks.ssm", "blocks.ssm.in_proj",
                "blocks.attn", "lm_head"} <= addrs


# ---------------------------------------------------------------------------
# PlacementPlan semantics
# ---------------------------------------------------------------------------

class TestPlacementPlan:
    def test_round_trips_through_config(self):
        cfg = FAMILY_CFGS["cnn"]
        p = plan.PlacementPlan.build(cfg, {
            "convs.0": {"memory": "sram"},
            "convs.2": {"engine": "dequant"}})
        model = deploy.compile_model(cfg, plan=p)
        back = plan.PlacementPlan.from_config(model.cfg)
        assert back.entries == p.entries
        assert back.spec("convs.0").enabled is False
        assert back.engine("convs.2") == "dequant"
        assert back.residency("convs.1") == "rom"     # default untouched

    def test_unknown_site_raises_with_valid_set(self):
        cfg = FAMILY_CFGS["cnn"]
        with pytest.raises(ValueError, match="convs.0"):
            plan.PlacementPlan.build(cfg, {"conv.0": {"memory": "sram"}})

    def test_duplicate_site_raises(self):
        cfg = FAMILY_CFGS["cnn"]
        sram = dataclasses.replace(cfg.rebranch, enabled=False)
        with pytest.raises(ValueError, match="duplicate"):
            plan.PlacementPlan.build(
                cfg, [("convs.0", sram), ("convs.0", sram)])

    def test_prefix_resolution_longest_wins(self):
        cfg = FAMILY_CFGS["hybrid"]
        sram = dataclasses.replace(cfg.rebranch, enabled=False)
        deq = dataclasses.replace(cfg.rebranch, trunk_impl="dequant")
        p = plan.PlacementPlan.build(
            cfg, {"blocks.ssm": sram, "blocks.ssm.x_proj": deq})
        assert p.residency("blocks.ssm.in_proj") == "sram"
        assert p.spec("blocks.ssm.x_proj") is deq     # longest prefix wins
        assert p.residency("blocks.attn") == "rom"
        # and spec_for agrees once folded into the config
        cfg2 = deploy.compile_model(cfg, plan=p).cfg
        assert spec_for(cfg2, "blocks.ssm.in_proj").enabled is False
        assert spec_for(cfg2, "blocks.ssm.x_proj").trunk_impl == "dequant"

    def test_plan_is_hashable_static(self):
        p = plan.PlacementPlan.build(FAMILY_CFGS["cnn"],
                                     {"convs.0": {"memory": "sram"}})
        hash(p)

    def test_stats_bookkeeping(self):
        cfg = FAMILY_CFGS["cnn"]
        s_all_rom = plan.PlacementPlan.build(cfg, {}).stats(cfg)
        assert s_all_rom.sram_sites == 0 and s_all_rom.sram_bits == 0
        assert s_all_rom.branch_bits > 0              # branches live
        sram = dataclasses.replace(cfg.rebranch, enabled=False)
        s_mix = plan.PlacementPlan.build(
            cfg, {"convs.0": sram}).stats(cfg)
        assert s_mix.sram_sites == 1
        assert s_mix.rom_bits < s_all_rom.rom_bits
        # total trunk bits conserved regardless of residency
        assert s_mix.weight_bits_total == s_all_rom.weight_bits_total
        # no-branch plan: ROM trunk only
        bare = dataclasses.replace(cfg.rebranch, branch_enabled=False)
        s_bare = plan.PlacementPlan(model=cfg.name, default=bare).stats(cfg)
        assert s_bare.branch_bits == 0 and s_bare.branch_macs == 0


# ---------------------------------------------------------------------------
# the cost-driven solver (Fig. 12)
# ---------------------------------------------------------------------------

class TestSolve:
    def test_darknet19_fig12_shape(self):
        """Mid-budget solve on DarkNet-19 reproduces the paper's Fig. 12
        qualitative shape: small early layers + late 1x1 bottlenecks go
        SRAM-trainable, the bulk wide mid/late 3x3 convs stay ROM."""
        from repro.configs.paper_models import PAPER_MODELS
        cfg = PAPER_MODELS["darknet19"]
        recs = plan.sweep(cfg, 5, reload_factor=3.0)
        mid = recs[1]["plan"]
        assert 0 < recs[1]["sram_sites"] < recs[1]["rom_sites"] \
            + recs[1]["sram_sites"]
        # early small convs flip to SRAM first
        assert mid.residency("convs.0") == "sram"
        assert mid.residency("convs.1") == "sram"
        # late 1x1 bottlenecks (512->256, 1024->512) are cheap: SRAM
        assert mid.residency("convs.9") == "sram"
        assert mid.residency("convs.16") == "sram"
        # the bulk wide 3x3 convs (3x3x512x1024 +) and head stay ROM
        for site in ("convs.13", "convs.15", "convs.17",
                     "head.0", "head.1"):
            assert mid.residency(site) == "rom", site

    def test_budget_monotone(self):
        from repro.configs.paper_models import PAPER_MODELS
        cfg = PAPER_MODELS["tiny_yolo"]
        recs = plan.sweep(cfg, 4)
        n_sram = [r["sram_sites"] for r in recs]
        assert n_sram == sorted(n_sram)
        assert n_sram[0] == 0                          # all-ROM floor
        assert n_sram[-1] == len(plan.site_tree(cfg))  # all-SRAM ceiling
        areas = [r["area_mm2"] for r in recs]
        assert all(a <= b + 1e-9 for a, b in zip(areas, recs and areas[1:]))
        # spending area buys energy headroom in the BASELINE's favour:
        # efficiency over iso-area SRAM shrinks toward 1x
        effs = [r["efficiency_x"] for r in recs]
        assert effs == sorted(effs, reverse=True)

    def test_budget_below_floor_clamps_to_all_rom(self):
        cfg = FAMILY_CFGS["cnn"]
        p = plan.solve(cfg, 0.001)
        assert all(s.enabled for _, s in p.entries) or not p.entries

    def test_solve_works_on_lm_families(self):
        """The planner is family-generic: an SSM config solves too."""
        cfg = FAMILY_CFGS["ssm"]
        stats = plan.solve(cfg).stats(cfg)
        assert stats.rom_sites == len(plan.site_tree(cfg))
        hi = plan.sweep(cfg, 3)[-1]
        assert hi["sram_sites"] == stats.rom_sites


# ---------------------------------------------------------------------------
# deploy integration: plan= is bit-identical to hand-written overrides
# ---------------------------------------------------------------------------

class TestDeployParity:
    @pytest.mark.parametrize("engine_name", ENGINES)
    def test_plan_equals_handwritten_overrides_cnn(self, engine_name):
        cfg = cnn.CNNConfig(name="tiny_yolo", input_size=16,
                            head_anchors=2, head_classes=3)
        overrides = {"convs.0": {"memory": "sram"},
                     "convs.2": {"memory": "sram"},
                     "head.0": {"memory": "sram"}}
        p = plan.PlacementPlan.build(
            cfg, overrides,
            default=dataclasses.replace(cfg.rebranch,
                                        trunk_impl=engine_name))
        m_plan = deploy.compile_model(cfg, plan=p)
        m_hand = deploy.compile_model(cfg, engine=engine_name,
                                      layer_overrides=overrides)
        assert m_plan.cfg == m_hand.cfg               # identical mapping
        params = m_plan.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 3))
        np.testing.assert_array_equal(
            np.asarray(m_plan.forward(params, x)),
            np.asarray(m_hand.forward(params, x)))

    def test_solved_plan_deploys_end_to_end(self):
        cfg = cnn.CNNConfig(name="vgg8", num_classes=7, input_size=16)
        budget = plan.sweep(cfg, 3)[1]["budget_mm2"]
        p = plan.solve(cfg, budget)
        model = deploy.compile_model(cfg, plan=p)
        params = model.init(jax.random.PRNGKey(0))
        # SRAM sites initialise as plain trainable convs (no ROM image)
        for site, spec in p.entries:
            if not spec.enabled and site.startswith("convs."):
                idx = int(site.split(".")[1])
                assert "rom" not in params["convs"][idx], site
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
        y = model.forward(params, x)
        assert y.shape == (2, 7) and bool(jnp.all(jnp.isfinite(y)))

    def test_plan_layer_overrides_mutually_exclusive(self):
        cfg = FAMILY_CFGS["cnn"]
        p = plan.PlacementPlan.build(cfg, {})
        with pytest.raises(ValueError, match="not both"):
            deploy.compile_model(cfg, plan=p,
                                 layer_overrides={"convs.0":
                                                  {"memory": "sram"}})

    def test_plan_replaces_stale_config_overrides(self):
        """An explicit plan is canonical: a leaf override already folded
        into the config must not out-length and shadow the plan's
        ancestor-prefix entry."""
        cfg = FAMILY_CFGS["transformer"]
        cfg2 = deploy.compile_model(
            cfg, layer_overrides={"blocks.attn": {"memory": "sram"}}).cfg
        rom = cfg2.rebranch                            # enabled=True
        p = plan.PlacementPlan.build(cfg2, {"blocks": rom})
        cfg3 = deploy.compile_model(cfg2, plan=p).cfg
        assert spec_for(cfg3, "blocks.attn").enabled is True
        assert spec_for(cfg3, "blocks.attn") is p.spec("blocks.attn")

    def test_plan_for_wrong_config_raises(self):
        p = plan.PlacementPlan.build(FAMILY_CFGS["cnn"], {})
        with pytest.raises(ValueError, match="vgg8"):
            deploy.compile_model(FAMILY_CFGS["cnn_resnet"], plan=p)

    def test_lm_prefix_plan_forward(self):
        """A 'blocks' prefix entry governs the refined sub-sites — the
        pre-refactor override surface keeps working."""
        cfg = FAMILY_CFGS["transformer"]
        sram = dataclasses.replace(cfg.rebranch, enabled=False)
        p = plan.PlacementPlan.build(cfg, {"blocks": sram})
        model = deploy.compile_model(cfg, plan=p)
        params = model.init(jax.random.PRNGKey(0))
        flat = jax.tree_util.tree_leaves_with_path(params["layers"])
        assert not any("rom" in jax.tree_util.keystr(kp) for kp, _ in flat)
        out = model.forward(params, {"tokens":
                                     jnp.ones((2, 4), jnp.int32)})
        assert out.shape == (2, 4, cfg.vocab_size)


# ---------------------------------------------------------------------------
# per-site overrides inside ssm / hybrid (newly wired families)
# ---------------------------------------------------------------------------

class TestSsmHybridSites:
    @pytest.mark.parametrize("family", ["ssm", "hybrid"])
    def test_per_site_override_changes_only_that_group(self, family):
        cfg = FAMILY_CFGS[family]
        prefix = "blocks" if family == "ssm" else "blocks.ssm"
        model = deploy.compile_model(
            cfg, layer_overrides={f"{prefix}.x_proj": {"memory": "sram"}})
        params = model.init(jax.random.PRNGKey(0))
        layer0 = (jax.tree.map(lambda a: a, params["layers"])
                  if cfg.scan_layers else params["layers"][0])
        blk = layer0["ssm"] if family == "hybrid" else layer0["ssm"]
        assert "rom" not in blk["x_proj"]              # flipped to SRAM
        assert "rom" in blk["in_proj"]                 # untouched
        out = model.forward(params,
                            {"tokens": jnp.ones((2, 4), jnp.int32)})
        assert bool(jnp.all(jnp.isfinite(out)))

    def test_spec_for_is_identity_without_overrides(self):
        cfg = FAMILY_CFGS["ssm"]
        assert spec_for(cfg, "blocks.in_proj") is cfg.rebranch


# ---------------------------------------------------------------------------
# plan-aware pricing stays wired to the Fig. 12 cost model
# ---------------------------------------------------------------------------

class TestCostWiring:
    def test_all_rom_area_tracks_energy_module(self):
        """plan area ~ core.energy.yoloc_area on the same net (same
        densities; plan adds the explicit C/U projection bits the
        branch_fraction shorthand folds away)."""
        from repro.configs.paper_models import PAPER_MODELS
        from repro.core import energy
        from benchmarks import netstats
        cfg = PAPER_MODELS["tiny_yolo"]
        stats = plan.solve(cfg).stats(cfg)
        got = plan.plan_area_mm2(stats)
        want = energy.yoloc_area(netstats.paper_net_stats()["tiny_yolo"])
        assert abs(got - want) / want < 0.30           # same ballpark
        # and the area RATIO to all-SRAM is Fig. 12's headline direction
        tree = plan.site_tree(cfg)
        all_sram_bits = sum(s.total_weights for s in tree) * 8
        cm = energy.DEFAULT_COST
        ratio = (all_sram_bits / 1e6 / cm.sram_density_mb_mm2) / got
        assert ratio > 5.0                             # ROM wins big
