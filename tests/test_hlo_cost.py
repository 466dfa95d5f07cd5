"""hlo_cost parser: exact FLOPs on known programs (matmul, scan, nested
scan, int8 dot, conv) and collective-byte extraction."""

import jax
import jax.numpy as jnp

from repro.launch import hlo_cost


def _cost(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return hlo_cost.analyse_text(txt)


class TestFlops:
    def test_plain_matmul(self):
        a, b = jnp.zeros((128, 64)), jnp.zeros((64, 32))
        c = _cost(lambda a, b: a @ b, a, b)
        assert c["flops"] == 2 * 128 * 64 * 32

    def test_int8_dot_counted(self):
        a = jnp.zeros((64, 32), jnp.int8)
        b = jnp.zeros((32, 16), jnp.int8)
        c = _cost(lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32), a, b)
        assert c["flops"] == 2 * 64 * 32 * 16

    def test_scan_trip_count(self):
        x, w = jnp.zeros((32, 32)), jnp.zeros((32, 32))

        def g(x, w):
            return jax.lax.scan(lambda c, _: (c @ w, None), x, None,
                                length=7)[0]
        c = _cost(g, x, w)
        assert c["flops"] == 7 * 2 * 32 ** 3

    def test_nested_scan(self):
        x, w = jnp.zeros((16, 16)), jnp.zeros((16, 16))

        def g(x, w):
            def outer(c, _):
                inner = jax.lax.scan(lambda ci, _: (ci @ w, None), c,
                                     None, length=3)[0]
                return inner, None
            return jax.lax.scan(outer, x, None, length=5)[0]
        c = _cost(g, x, w)
        assert c["flops"] == 15 * 2 * 16 ** 3

    def test_conv_flops(self):
        x = jnp.zeros((1, 8, 8, 4))
        k = jnp.zeros((3, 3, 4, 8))

        def f(x, k):
            return jax.lax.conv_general_dilated(
                x, k, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        c = _cost(f, x, k)
        # 2 * out_elems * (kh*kw*cin)
        assert c["flops"] == 2 * (8 * 8 * 8) * (3 * 3 * 4)


class TestCollectives:
    def test_sharded_allreduce_bytes(self):
        import subprocess, sys, os, textwrap
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["PYTHONPATH"] = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "src")
        code = textwrap.dedent("""
            import jax, jax.numpy as jnp
            from repro.launch.mesh import make_mesh
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.launch import hlo_cost
            mesh = make_mesh((8,), ('x',))
            def f(a, b):
                y = a @ b                     # contraction sharded -> psum
                return y
            a = jax.ShapeDtypeStruct((32, 64), jnp.float32)
            b = jax.ShapeDtypeStruct((64, 16), jnp.float32)
            with mesh:
                c = jax.jit(f, in_shardings=(
                    NamedSharding(mesh, P(None, 'x')),
                    NamedSharding(mesh, P('x', None)))).lower(a, b).compile()
            costs = hlo_cost.analyse_text(c.as_text())
            assert costs['collective_bytes'] >= 32 * 16 * 4, costs
            print('OK', costs['collective_bytes'])
        """)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=180)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout
