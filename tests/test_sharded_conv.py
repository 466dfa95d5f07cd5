"""Halo-exchange sharded conv: parity with the unsharded Pallas engine.

The 'pallas_sharded' contract is BIT-identity: per-device results equal
the single-device 'pallas' engine exactly (same per-row quantisation,
same k-block accumulation order — see kernels/halo_conv.py).  Multi-
device cases run in subprocesses with forced host devices (kept OUT of
this process so other tests see 1 device, per the dry-run rule); the
halo *plan* math and the no-mesh fallback are tested in-process.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# halo plan math (pure, no devices)
# ---------------------------------------------------------------------------

class TestHaloPlan:
    def test_aligned_stride1(self):
        from repro.kernels.halo_conv import plan_halo
        p = plan_halo(16, 3, 1, "SAME", 4)
        assert p.aligned and (p.top, p.bot) == (1, 1)
        assert (p.pad_top, p.pad_bot) == (0, 0)
        assert (p.oh, p.ol) == (16, 4)

    def test_aligned_stride2_pads_bottom_only(self):
        from repro.kernels.halo_conv import plan_halo
        # SAME s=2 k=3 on even H: ph0=0, all halo flows upward
        p = plan_halo(16, 3, 2, "SAME", 4)
        assert p.aligned and (p.top, p.bot) == (0, 1)
        assert (p.oh, p.ol) == (8, 2)

    def test_no_halo_1x1(self):
        from repro.kernels.halo_conv import plan_halo
        p = plan_halo(16, 1, 1, "SAME", 4)
        assert p.aligned and (p.top, p.bot) == (0, 0)

    def test_uneven_h_general_path(self):
        from repro.kernels.halo_conv import plan_halo
        p = plan_halo(9, 3, 2, "SAME", 4)     # oh=5, ph0=1
        assert not p.aligned
        assert p.pad_top == 1                  # materialised global top pad
        assert p.n * p.ol >= p.oh              # all outputs covered
        # materialised rows cover every real input row
        assert p.pad_top + 9 + p.pad_bot == p.n * p.ol * 2

    def test_infeasible_returns_none(self):
        from repro.kernels.halo_conv import plan_halo
        # 5x5 kernel, 1-row shards: halo spans >1 neighbour -> None
        assert plan_halo(4, 5, 1, "SAME", 4) is None

    def test_halo_bytes(self):
        from repro.kernels.halo_conv import halo_bytes
        # 3x3 stride-1: 2 halo rows x N2 x W8 x C20 x 4B
        assert halo_bytes((2, 16, 8, 20), 3, 1, "SAME", 4) == 2 * 2 * 8 * 20 * 4
        assert halo_bytes((2, 16, 8, 20), 1, 1, "SAME", 4) == 0


# ---------------------------------------------------------------------------
# no-mesh fallback (in-process, 1 device)
# ---------------------------------------------------------------------------

class TestFallback:
    def test_registered_with_honest_capabilities(self):
        from repro import engine
        eng = engine.get("pallas_sharded")
        assert eng.capabilities.sharded_ops == ("conv",)
        assert eng.capabilities.epilogue

    def test_no_mesh_falls_back_to_pallas(self):
        import jax
        import numpy as np
        from repro import engine
        from repro.core import cim as cim_lib
        from repro.core import rebranch
        from repro.models import cnn

        cfg = cim_lib.CiMConfig(mode="ideal")
        p = cnn.init_conv(jax.random.PRNGKey(0), 3, 20, 12,
                          rebranch.ReBranchSpec())
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 20))
        w_q, w_scale = p["rom"]["w_q"], p["rom"]["w_scale"]
        got = engine.get("pallas_sharded").conv(cfg, x, w_q, w_scale)
        want = engine.get("pallas").conv(cfg, x, w_q, w_scale)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_no_mesh_fallback_is_silent(self):
        """Running without a mesh is normal single-device operation, not a
        surprise — no warning."""
        import warnings

        import jax
        from repro import engine
        from repro.core import cim as cim_lib
        from repro.core import rebranch
        from repro.models import cnn

        p = cnn.init_conv(jax.random.PRNGKey(0), 3, 8, 8,
                          rebranch.ReBranchSpec())
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8, 8))
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            engine.get("pallas_sharded").conv(
                cim_lib.CiMConfig(mode="ideal"), x,
                p["rom"]["w_q"], p["rom"]["w_scale"])


def test_halo_doesnt_fit_fallback_warns_once():
    """When a mesh IS bound but the halo would span more than one
    neighbour shard, the engine must say so (once per geometry) instead
    of silently dropping the sharding the deployment asked for."""
    out = _run(textwrap.dedent("""
        import warnings
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro import engine as engine_lib
        from repro.core import cim as cim_lib
        from repro.core import rebranch
        from repro.distributed import sharding as shd
        from repro.models import cnn

        cfg = cim_lib.CiMConfig(mode="ideal")
        p = cnn.init_conv(jax.random.PRNGKey(0), 5, 8, 8,
                          rebranch.ReBranchSpec())
        # H=8 over 8 shards -> 1 row/shard < the 5x5 kernel's 2-row halo:
        # infeasible, the engine must fall back unsharded (and say so)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8, 8))
        mesh = make_mesh((8, 1), ("data", "model"))
        eng = engine_lib.get("pallas_sharded")
        with shd.use_mesh(mesh), mesh:
            with warnings.catch_warnings(record=True) as w1:
                warnings.simplefilter("always")
                y = eng.conv(cfg, x, p["rom"]["w_q"], p["rom"]["w_scale"])
            with warnings.catch_warnings(record=True) as w2:
                warnings.simplefilter("always")
                y = eng.conv(cfg, x, p["rom"]["w_q"], p["rom"]["w_scale"])
        hits1 = [m for m in w1 if "falling back" in str(m.message)]
        hits2 = [m for m in w2 if "falling back" in str(m.message)]
        print("WARNED_FIRST", len(hits1))
        print("WARNED_AGAIN", len(hits2))
        print("MSG_OK", "halo for H=8 kh=5" in str(hits1[0].message)
              if hits1 else False)
    """))
    assert "WARNED_FIRST 1" in out, out
    assert "WARNED_AGAIN 0" in out, out          # one-time per geometry
    assert "MSG_OK True" in out, out


# ---------------------------------------------------------------------------
# multi-device bit-parity (subprocess, forced host devices)
# ---------------------------------------------------------------------------

def test_sharded_trunk_conv_bit_identical_sweep():
    """pallas_sharded == pallas bit-for-bit over 1/2/4-way H-sharded
    meshes, stride {1,2}, kernels {1x1, 3x3}, even and odd H (the kh=1
    no-halo fast path and the uneven-shard general path included)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro import engine as engine_lib
        from repro.core import cim as cim_lib
        from repro.distributed import sharding as shd
        from repro.models import cnn
        from repro.core import rebranch

        cfg = cim_lib.CiMConfig(mode='ideal')
        eng_sh = engine_lib.get('pallas_sharded')
        eng_pl = engine_lib.get('pallas')
        key = jax.random.PRNGKey(0)
        checked = 0
        for n_dev in (1, 2, 4):
            mesh = make_mesh((n_dev, 1), ('data', 'model'),
                             devices=jax.devices()[:n_dev])
            for k in (1, 3):
                p = cnn.init_conv(jax.random.fold_in(key, k), k, 20, 12,
                                  rebranch.ReBranchSpec())
                w_q, w_scale = p['rom']['w_q'], p['rom']['w_scale']
                for stride in (1, 2):
                    for h in (16, 9):       # even (aligned) and odd (uneven)
                        x = jax.random.normal(
                            jax.random.fold_in(key, 100 + h), (2, h, 8, 20))
                        want = eng_pl.conv(cfg, x, w_q, w_scale,
                                           stride=stride)
                        with shd.use_mesh(mesh), mesh:
                            got = jax.jit(lambda x: eng_sh.conv(
                                cfg, x, w_q, w_scale, stride=stride))(x)
                        np.testing.assert_array_equal(
                            np.asarray(got), np.asarray(want),
                            err_msg=f'n={n_dev} k={k} s={stride} h={h}')
                        checked += 1
        print('OK', checked)
    """)
    assert "OK 24" in out


def test_sharded_conv_fidelity_modes():
    """Bit-parity holds in the non-ideal CiM modes too (the ADC transfer
    is per-(row, subarray) — the halo exchange preserves both).  Both
    sides are jit'd: eager vs jit of the SAME unsharded program already
    differs by 1 ulp in per_subarray mode (XLA fuses the f32 ADC chain
    differently), so the parity contract is under a common pipeline."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro import engine as engine_lib
        from repro.core import cim as cim_lib, rebranch
        from repro.distributed import sharding as shd
        from repro.models import cnn

        mesh = make_mesh((4, 1), ('data', 'model'),
                         devices=jax.devices()[:4])
        p = cnn.init_conv(jax.random.PRNGKey(0), 3, 20, 12,
                          rebranch.ReBranchSpec())
        w_q, w_scale = p['rom']['w_q'], p['rom']['w_scale']
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 8, 20))
        for mode in ('per_subarray', 'bitserial'):
            cfg = cim_lib.CiMConfig(mode=mode)
            want = jax.jit(lambda x: engine_lib.get('pallas')
                           .conv(cfg, x, w_q, w_scale))(x)
            with shd.use_mesh(mesh), mesh:
                got = jax.jit(lambda x: engine_lib.get('pallas_sharded')
                              .conv(cfg, x, w_q, w_scale))(x)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=mode)
        print('OK')
    """, devices=4)
    assert "OK" in out


def test_sharded_rebranch_conv_and_ste_grad():
    """The fused sharded ReBranch conv matches its unsharded twin to
    1 ulp (the branch sketch is a float GEMM — BLAS reduction order is
    shape-dependent, so bitwise equality is a trunk-only property), and
    the sharded trunk's STE backward equals the vjp of the dequantised
    XLA conv."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import cim as cim_lib, rebranch
        from repro.distributed import sharding as shd
        from repro.kernels import halo_conv
        from repro.kernels.rebranch_conv import rebranch_conv_pallas
        from repro.models import cnn

        mesh = make_mesh((4, 1), ('data', 'model'),
                         devices=jax.devices()[:4])
        cfg = cim_lib.CiMConfig(mode='ideal')
        p = cnn.init_conv(jax.random.PRNGKey(0), 3, 20, 12,
                          rebranch.ReBranchSpec())
        p['sram']['core'] = jax.random.normal(
            jax.random.PRNGKey(2), p['sram']['core'].shape) * 0.05
        rom, sram = p['rom'], p['sram']
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 8, 20))

        want = jax.jit(lambda x: rebranch_conv_pallas(
            x, rom['w_q'], rom['w_scale'], rom['C'], sram['core'],
            rom['U'], cfg))(x)
        with shd.use_mesh(mesh), mesh:
            got = jax.jit(lambda x: halo_conv.sharded_rebranch_conv(
                x, rom['w_q'], rom['w_scale'], rom['C'], sram['core'],
                rom['U'], cfg, mesh=mesh, axis='data'))(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=2e-6)

        w_q, w_scale = rom['w_q'], rom['w_scale']
        with shd.use_mesh(mesh), mesh:
            dx = jax.grad(lambda x: jnp.sum(halo_conv.sharded_trunk_conv(
                cfg, 2, 'SAME', mesh, 'data', x, w_q, w_scale)))(x)
        w_deq = w_q.astype(jnp.float32) * w_scale.astype(jnp.float32)
        want_dx = jax.grad(lambda x: jnp.sum(rebranch.conv_nhwc(
            x, w_deq, 2, 'SAME')))(x)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(want_dx),
                                   rtol=1e-4, atol=1e-4)
        print('OK')
    """, devices=4)
    assert "OK" in out


def test_darknet_and_resnet_trunk_convs_bit_identical():
    """Acceptance shape: every distinct trunk-conv geometry of DarkNet-19
    and ResNet-18 (at a reduced input) is bit-identical between the
    sharded and unsharded engines on a 4-device mesh."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro import engine as engine_lib
        from repro.core import cim as cim_lib, rebranch
        from repro.distributed import sharding as shd
        from repro.models import cnn

        mesh = make_mesh((4, 1), ('data', 'model'),
                         devices=jax.devices()[:4])
        cfg = cim_lib.CiMConfig(mode='ideal')
        eng_sh = engine_lib.get('pallas_sharded')
        eng_pl = engine_lib.get('pallas')
        key = jax.random.PRNGKey(0)

        # (c_in, c_out, k, h, stride) trunk-conv geometries at 32px input
        geoms = set()
        h, c_in = 32, 3
        for item in cnn.DARKNET19:
            if item == 'M':
                h //= 2
                continue
            c, k = item
            geoms.add((c_in, c, k, h, 1))
            c_in = c
        h, c_in = 32, 64                       # resnet18 stem is 3->64
        geoms.add((3, 64, 3, 32, 1))
        for c_out, blocks, stride in cnn.RESNET18_STAGES:
            geoms.add((c_in, c_out, 3, h, stride))       # conv1 (+proj 1x1)
            if stride != 1 or c_in != c_out:
                geoms.add((c_in, c_out, 1, h, stride))
            h //= stride
            geoms.add((c_out, c_out, 3, h, 1))           # conv2
            c_in = c_out

        for i, (ci, co, k, h, s) in enumerate(sorted(geoms)):
            # cap channels: parity is channel-independent, runtime is not
            ci_t, co_t = min(ci, 64), min(co, 64)
            p = cnn.init_conv(jax.random.fold_in(key, i), k, ci_t, co_t,
                              rebranch.ReBranchSpec())
            w_q, w_scale = p['rom']['w_q'], p['rom']['w_scale']
            x = jax.random.normal(jax.random.fold_in(key, 1000 + i),
                                  (1, h, h, ci_t))
            want = eng_pl.conv(cfg, x, w_q, w_scale, stride=s)
            with shd.use_mesh(mesh), mesh:
                got = jax.jit(lambda x: eng_sh.conv(
                    cfg, x, w_q, w_scale, stride=s))(x)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want),
                err_msg=f'cin={ci_t} cout={co_t} k={k} h={h} s={s}')
        print('OK', len(geoms))
    """, devices=4)
    assert "OK" in out


def test_compile_model_mesh_cnn_forward():
    """deploy.compile_model(cfg, mesh=...) serves a whole H-sharded CNN:
    forward matches the unsharded engine to f32 tolerance (the XLA branch
    convs repartition under GSPMD, so full-model parity is allclose, not
    bit-equal — the trunk convs themselves are covered bit-exactly above).
    """
    out = _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro import deploy
        from repro.core import cim as cim_lib, rebranch
        from repro.models import cnn

        mesh = make_mesh((4, 1), ('data', 'model'),
                         devices=jax.devices()[:4])
        spec = dataclasses.replace(rebranch.ReBranchSpec(),
                                   cim=cim_lib.CiMConfig(mode='ideal'))
        for name in ('darknet19', 'resnet18'):
            cfg = cnn.CNNConfig(name=name, input_size=32, rebranch=spec,
                                fuse_bn_act=True)
            sharded = deploy.compile_model(cfg, engine='pallas_sharded',
                                           mesh=mesh)
            plain = deploy.compile_model(cfg, engine='pallas')
            params = plain.init(jax.random.PRNGKey(0))
            x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
            want = plain.forward(params, x)
            got = jax.jit(sharded.forward)(params, x)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4, err_msg=name)
        print('OK')
    """, devices=4)
    assert "OK" in out


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
