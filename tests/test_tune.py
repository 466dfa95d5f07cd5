"""Kernel autotuner + tuning table (repro.tune).

The load-bearing contract: a tuning-table entry may change how fast a
kernel runs, NEVER what it returns.  Covers:

  * table mechanics — round-trip determinism, lookup fallback on unseen
    keys, overrides()/disabled() context stack;
  * legality — legal_block_ks only emits block_k values reproducing the
    default k-partition, candidates() orders direct-first, and the
    resolve_tiling k-partition guard drops hand-edited illegal entries;
  * bit parity — the checked-in table resolves bit-identically to the
    untuned defaults through the real kernels in all three fidelity
    modes, grid dim-order / block-shape candidates are bit-identical to
    each other, and explicit block_k clamping is value-neutral;
  * dispatch — the pallas_fused engine routes live-branch sites through
    the fused kernels, and deploy.compile_model's tune= gate.
"""

import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import deploy, engine
from repro.core import cim as cim_lib
from repro.core.rebranch import ReBranchSpec
from repro.kernels.cim_matmul import cim_matmul_pallas
from repro.kernels.tiling import k_partition, resolve_tiling
from repro.models import cnn
from repro.tune import autotune, table

# the package re-exports jitted ops shadowing the submodule name
_rc = importlib.import_module("repro.kernels.rebranch_conv")

MODES = ["ideal", "per_subarray", "bitserial"]


def _conv_inputs(key, kk, c_in, c_out, hw):
    x = jax.random.normal(key, (1, hw, hw, c_in), jnp.float32)
    w_q = jax.random.randint(jax.random.fold_in(key, 1),
                             (kk, kk, c_in, c_out), -127, 128, jnp.int8)
    w_scale = jnp.full((c_out,), 0.01, jnp.float32)
    return x, w_q, w_scale


# ---------------------------------------------------------------------------
# table mechanics
# ---------------------------------------------------------------------------

class TestTable:
    def test_round_trip_and_determinism(self, tmp_path):
        entries = {
            table.key("trunk_conv", "ideal", "float32", 64, 576, 128):
                table.Tiling(128, 128, 512, "kmn", "direct"),
            table.key("cim_matmul", "bitserial", "int8", 16, 288, 32):
                table.Tiling(64, 64, 384, "mnk", "grid"),
        }
        p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
        table.save_table(entries, str(p1), meta={"models": ["x"]})
        table.save_table(dict(reversed(list(entries.items()))), str(p2),
                         meta={"models": ["x"]})
        # insertion order must not leak into the bytes (CI diffs on this)
        assert p1.read_bytes() == p2.read_bytes()
        loaded = {k: table.Tiling.from_json(v)
                  for k, v in json.loads(p1.read_text())["entries"].items()}
        assert loaded == entries

    def test_lookup_unseen_key_is_none(self):
        assert table.lookup("trunk_conv", "ideal", "float32",
                            7, 7919, 13) is None

    def test_overrides_and_disabled_stack(self):
        k = table.key("trunk_conv", "ideal", "float32", 8, 256, 8)
        t = table.Tiling(64, 64, 256, "mnk", "direct")
        with table.overrides({k: t}):
            assert table.lookup("trunk_conv", "ideal", "float32",
                                8, 256, 8) == t
            with table.disabled():
                assert table.lookup("trunk_conv", "ideal", "float32",
                                    8, 256, 8) is None
            assert table.lookup("trunk_conv", "ideal", "float32",
                                8, 256, 8) == t

    def test_table_tuned_on_another_backend_is_ignored(self, tmp_path,
                                                       monkeypatch):
        """A table's entries apply only on the backend named in its
        meta: CPU-tuned 'direct' entries must not steer the TPU path."""
        geo = ("trunk_conv", "ideal", "float32", 8, 256, 8)
        t = table.Tiling(64, 64, 256, "mnk", "direct")
        for backend, want in ((jax.default_backend(), t),
                              ("another-backend", None)):
            p = tmp_path / f"{backend}.json"
            table.save_table({table.key(*geo): t}, str(p),
                             meta={"backend": backend})
            monkeypatch.setattr(table, "_DEFAULT_PATH", str(p))
            assert table.lookup(*geo) == want, backend

    def test_tiling_validation(self):
        with pytest.raises(ValueError):
            table.Tiling(128, 128, 512, dim_order="nkm")
        with pytest.raises(ValueError):
            table.Tiling(128, 128, 512, impl="magic")

    def test_checked_in_table_is_consistent(self):
        # the CI smoke step (python -m repro.tune --check) as a test
        assert autotune.check_table(log=lambda *a, **k: None)

    def test_batched_geometries_scale_the_m_axis(self):
        """Serving batch sizes enumerate DISTINCT table keys: the patch
        GEMM's M axis is batch*OH*OW, so a micro-batched CNNServer
        dispatch must not fall back to untuned defaults."""
        solo = autotune.conv_geometries(
            ("tiny_yolo",), (32,), ("ideal",), ("trunk_conv",))
        both = autotune.conv_geometries(
            ("tiny_yolo",), (32,), ("ideal",), ("trunk_conv",),
            batches=(1, 8))
        solo_keys = {g.key for g in solo}
        assert solo_keys < {g.key for g in both}       # strict superset
        by_shape = {(g.m, g.k, g.n): g for g in both}
        for g in solo:
            batched = by_shape.get((8 * g.m, g.k, g.n))
            assert batched is not None, f"no batch-8 twin for {g.key}"
            assert batched.conv[5] == 8 and g.conv[5] == 1
        # meta round-trip: a table generated with batches checks clean
        # against the same enumeration (and a legacy table without the
        # key falls back to solo-only)
        assert autotune.conv_geometries(
            ("tiny_yolo",), (32,), ("ideal",), ("trunk_conv",),
            batches=(1,)) == solo


# ---------------------------------------------------------------------------
# legality
# ---------------------------------------------------------------------------

class TestLegality:
    @pytest.mark.parametrize("k,expect", [
        (288, [384]),     # round_up(288,128)=384: 128/256 split it, 512 dups
        (576, [512]),     # two-block partition — only the default survives
        (64, [128]),      # sub-subarray contraction clamps everything to 128
    ])
    def test_legal_block_ks(self, k, expect):
        assert autotune.legal_block_ks(k) == expect
        base = k_partition(k, 512, 128)
        for bk in autotune.legal_block_ks(k):
            assert k_partition(k, bk, 128) == base

    def test_candidates_direct_first_and_legal(self):
        cands = autotune.candidates("trunk_conv", 64, 576, 128, fast=True)
        assert cands[0].impl == "direct"
        base = k_partition(576, 512, 128)
        for c in cands:
            assert k_partition(576, c.block_k, 128) == base
        # fast sweep: impl/dim-order only, no block_m/n fan-out
        assert {(c.block_m, c.block_n) for c in cands
                if c.impl == "grid"} == {(128, 128)}

    def test_resolve_tiling_explicit_beats_table(self):
        k = table.key("trunk_conv", "ideal", "float32", 64, 576, 128)
        with table.overrides({k: table.Tiling(256, 256, 512,
                                              "kmn", "direct")}):
            t = resolve_tiling("trunk_conv", "ideal", "float32", 64, 576,
                               128, block_m=32, block_n=None, block_k=None,
                               defaults=(128, 128, 512), rows=128)
        # any explicit block size disables the lookup entirely
        assert (t.block_m, t.block_n, t.block_k) == (32, 128, 512)
        assert t.dim_order == "mnk"

    def test_resolve_tiling_drops_illegal_block_k(self):
        # a hand-edited entry that would split the 576-contraction into
        # 128-blocks — different per-block quant scales, different bits
        k = table.key("trunk_conv", "ideal", "float32", 64, 576, 128)
        with table.overrides({k: table.Tiling(128, 128, 128,
                                              "mnk", "direct")}):
            t = resolve_tiling("trunk_conv", "ideal", "float32", 64, 576,
                               128, block_m=None, block_n=None, block_k=None,
                               defaults=(128, 128, 512), rows=128)
        assert t.block_k == 512


# ---------------------------------------------------------------------------
# bit parity through the real kernels
# ---------------------------------------------------------------------------

class TestBitParity:
    @pytest.mark.parametrize("mode", MODES)
    def test_checked_in_table_is_bit_neutral(self, mode):
        """Shipping-table resolution == untuned defaults, exactly.

        Two geometries: gk=1 (288-wide patch rows) and gk=2 (576-wide,
        ragged 64-column tail) — the regimes the direct lowering
        dispatches differently.
        """
        cfg = cim_lib.CiMConfig(mode=mode)
        for kk, c_in, c_out, hw in [(3, 32, 32, 8), (3, 64, 32, 4)]:
            x, w_q, w_scale = _conv_inputs(
                jax.random.PRNGKey(hw), kk, c_in, c_out, hw)
            with table.disabled():
                ref = np.asarray(_rc.trunk_conv_pallas(x, w_q, w_scale, cfg))
            out = np.asarray(_rc.trunk_conv_pallas(x, w_q, w_scale, cfg))
            assert np.array_equal(ref, out), (mode, c_in)

    @pytest.mark.parametrize("mode", MODES)
    def test_fused_conv_table_bit_neutral(self, mode):
        cfg = cim_lib.CiMConfig(mode=mode)
        key = jax.random.PRNGKey(3)
        p = cnn.init_conv(key, 3, 64, 32, ReBranchSpec())
        x = jax.random.normal(jax.random.fold_in(key, 1), (1, 4, 4, 64))
        rom, sram = p["rom"], p["sram"]
        args = (x, rom["w_q"], rom["w_scale"], rom["C"], sram["core"],
                rom["U"])
        with table.disabled():
            ref = np.asarray(_rc.rebranch_conv_pallas(*args, cfg))
        assert np.array_equal(ref, np.asarray(
            _rc.rebranch_conv_pallas(*args, cfg))), mode

    def test_grid_candidates_bit_identical_to_each_other(self):
        """dim_order / block-shape moves never touch the grid's bits.

        (grid-vs-DIRECT is tolerance-equal only — different f32
        intermediates — which is why the autotuner verifies candidates
        empirically against the default path and drops mismatches
        instead of tabulating them.)
        """
        cfg = cim_lib.CiMConfig(mode="ideal")
        x, w_q, w_scale = _conv_inputs(jax.random.PRNGKey(7), 3, 64, 32, 4)
        geo_key = table.key("trunk_conv", "ideal", "float32",
                            16, 576, 32)
        outs = []
        for cand in autotune.candidates("trunk_conv", 16, 576, 32,
                                        fast=True):
            if cand.impl != "grid":
                continue
            with table.overrides({geo_key: cand}):
                outs.append(np.asarray(_rc.trunk_conv_pallas(
                    x, w_q, w_scale, cfg, interpret=True)))
        assert len(outs) >= 2           # both dim orders raced
        for o in outs[1:]:
            assert np.array_equal(outs[0], o)

    def test_cim_matmul_block_k_clamp_value_neutral(self):
        # k=64 < rows_per_subarray: every block_k clamps to one
        # 128-padded block, so explicit sizes can't change the result
        cfg = cim_lib.CiMConfig(mode="per_subarray")
        key = jax.random.PRNGKey(11)
        x_q = jax.random.randint(key, (32, 64), -127, 128, jnp.int8)
        w_q = jax.random.randint(jax.random.fold_in(key, 1),
                                 (64, 48), -127, 128, jnp.int8)
        a = np.asarray(cim_matmul_pallas(x_q, w_q, cfg, block_k=512))
        b = np.asarray(cim_matmul_pallas(x_q, w_q, cfg, block_k=128))
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# dispatch: fused engine + deploy gate
# ---------------------------------------------------------------------------

class TestDispatch:
    def test_pallas_fused_capabilities(self):
        eng = engine.get("pallas_fused")
        assert eng.capabilities.tune
        assert set(eng.capabilities.fused_ops) == {"conv", "matmul"}
        assert not eng.capabilities.grads       # inference-only fast path

    def test_fused_engine_matches_unfused_pallas(self):
        key = jax.random.PRNGKey(5)
        p = cnn.init_conv(key, 3, 32, 32, ReBranchSpec())
        x = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, 8, 32))
        y_ref = cnn.apply_conv(p, x, ReBranchSpec(trunk_impl="pallas"))
        y_fused = cnn.apply_conv(p, x, ReBranchSpec(trunk_impl="pallas_fused"))
        # identical trunk bits; the branch legs associate differently
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_fused),
                                   rtol=2e-4, atol=2e-4)

    def test_compile_model_tune_gate(self):
        cfg = cnn.CNNConfig(name="vgg8", num_classes=13, input_size=16)
        with pytest.raises(ValueError, match="tune=True"):
            deploy.compile_model(cfg, engine="dequant", tune=True)
        # table-aware engines pass the gate; tune=False binds the
        # baseline (table-disabled) policy without complaint
        assert deploy.compile_model(cfg, engine="pallas",
                                    tune=True).tune is True
        assert deploy.compile_model(cfg, engine="dequant",
                                    tune=False).tune is False
