"""Distribution-layer tests that need >1 device: run small sharded
programs in a subprocess with forced host devices (kept OUT of this
process so other tests see 1 device, per the dry-run rule)."""

import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 300) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """The sharded train step on a 4x2 mesh computes the same loss as the
    unsharded one — sharding is semantics-preserving."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro import configs, optim
        from repro.core import rebranch
        from repro.data import synthetic
        from repro.distributed import sharding as shd
        from repro.launch import steps as steps_lib

        cfg = configs.get_smoke('gemma_2b')
        dcfg = synthetic.DataConfig(seed=0, vocab_size=cfg.vocab_size,
                                    seq_len=32, global_batch=8)
        params = jax.tree.map(lambda x: x,
                              __import__('repro.models.api', fromlist=['x'])
                              .init(jax.random.PRNGKey(0), cfg))
        t, f = rebranch.partition(params)
        opt = optim.init(t)
        batch = synthetic.markov_batch(dcfg, 0)
        step = steps_lib.make_train_step(cfg, optim.AdamWConfig(lr=1e-3),
                                         loss_chunks=2)

        # single device
        _, _, m1 = jax.jit(step)(t, f, opt, batch)

        # sharded 4x2 mesh
        mesh = make_mesh((4, 2), ('data', 'model'))
        with shd.use_mesh(mesh), mesh:
            t_sh, f_sh, opt_sh, _ = steps_lib.model_state_shardings(cfg, mesh)
            in_sh = steps_lib.batch_shardings(
                cfg, mesh,
                steps_lib.input_specs(cfg, 32, 8, 'train'), 8)
            jstep = jax.jit(step, in_shardings=(t_sh, f_sh, opt_sh, in_sh))
            _, _, m2 = jstep(t, f, opt, batch)
        l1, l2 = float(m1['loss']), float(m2['loss'])
        assert abs(l1 - l2) < 2e-2 * max(abs(l1), 1.0), (l1, l2)
        print('OK', l1, l2)
    """)
    assert "OK" in out


def test_serve_step_sharded_decode():
    """Sharded decode on a mesh produces the same next token."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro import configs
        from repro.distributed import sharding as shd
        from repro.launch import steps as steps_lib
        from repro.models import api

        cfg = configs.get_smoke('yi_34b')
        params = api.init(jax.random.PRNGKey(0), cfg)
        cache = api.init_cache(cfg, 8, 32, dtype=jnp.float32)
        batch = {'tokens': jnp.ones((8, 1), jnp.int32)}
        step = steps_lib.make_serve_step(cfg)
        tok1, _ = jax.jit(step)(params, batch, cache)

        mesh = make_mesh((4, 2), ('data', 'model'))
        with shd.use_mesh(mesh), mesh:
            t_sh, f_sh, _, _ = steps_lib.model_state_shardings(cfg, mesh)
            from repro.core import rebranch
            c_sh = steps_lib.cache_shardings(cfg, mesh, cache)
            in_sh = steps_lib.batch_shardings(
                cfg, mesh, steps_lib.input_specs(cfg, 32, 8, 'decode'), 8)
            jstep = jax.jit(step, in_shardings=(
                rebranch.combine(t_sh, f_sh), in_sh, c_sh))
            tok2, _ = jstep(params, batch, cache)
        same = float(jnp.mean((tok1 == tok2).astype(jnp.float32)))
        assert same > 0.99, same
        print('OK', same)
    """)
    assert "OK" in out


def test_int8_compressed_allreduce_matches_plain():
    """shard_map int8 EF all-reduce ~= plain psum mean over the data axis."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.optim import compress

        mesh = make_mesh((8,), ('data',))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 1e-3
        err = jnp.zeros((8, 64))

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(P('data'), P('data')),
                 out_specs=(P('data'), P('data')))
        def compressed(gs, es):
            r, e = compress.all_reduce_int8(gs[0], es[0], 'data')
            return r[None], e[None]

        red, _ = compressed(g, err)
        want = jnp.mean(g, axis=0)
        got = red[0]
        err_rel = float(jnp.max(jnp.abs(got - want)) /
                        (jnp.max(jnp.abs(want)) + 1e-12))
        assert err_rel < 0.05, err_rel
        print('OK', err_rel)
    """)
    assert "OK" in out


def test_elastic_restore_across_meshes(tmp_path):
    """Checkpoint on an 8-device mesh, restore on 4 devices (elastic)."""
    out = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro import configs, optim
        from repro.checkpoint import manager as ckpt
        from repro.core import rebranch
        from repro.distributed import sharding as shd
        from repro.models import api

        cfg = configs.get_smoke('gemma_2b')
        params = api.init(jax.random.PRNGKey(0), cfg)
        t, f = rebranch.partition(params)
        opt = optim.init(t)
        ckpt.save({str(tmp_path)!r}, 3, t, opt, params)

        # restore re-sharded onto a DIFFERENT (smaller) mesh
        mesh = make_mesh((2, 2), ('data', 'model'))
        with shd.use_mesh(mesh), mesh:
            from repro.launch import steps as steps_lib
            t_sh, f_sh, opt_sh, _ = steps_lib.model_state_shardings(cfg, mesh)
            t_only, _ = rebranch.partition(
                jax.tree.map(lambda x: x, params))
            step, t2, opt2, _ = ckpt.restore(
                {str(tmp_path)!r}, t, opt, params,
                shardings=(t_sh, opt_sh))
        assert step == 3
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(t2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print('OK')
    """)
    assert "OK" in out
