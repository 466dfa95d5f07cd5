"""The persistent compilation cache lands where the environment says, or
at the fixed git-ignored path in the checkout.  Each case runs in its own
interpreter: the helper changes process-wide JAX settings."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env_dir: str | None) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_cache_lands_in_env_dir(tmp_path):
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print("DIR", enable_compile_cache())
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
    """, str(tmp_path))
    assert f"DIR {tmp_path}" in out
    assert any(tmp_path.iterdir()), "no cache entry was written"


def test_default_dir_is_fixed_in_checkout():
    out = _run("""
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        print("DIR", enable_compile_cache())
        print("JAX", jax.config.jax_compilation_cache_dir)
    """, None)
    want = os.path.join(REPO, ".jax_cache")
    assert f"DIR {want}\n" in out and f"JAX {want}\n" in out, out
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
