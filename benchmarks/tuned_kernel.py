"""Tuning-table payoff: untuned dispatch vs table-tuned dispatch.

Times ``kernels.rebranch_conv`` on DarkNet-19 patch-GEMM geometries
under three tiling resolutions:

  grid    : the ``pallas_call`` macro grid, forced via ``interpret=True``
            (off-TPU this is the interpreter — the dispatch the seed
            benchmarks ran before the tuning table existed)
  default : direct lowering with the per-kernel default tiling, table
            lookups disabled (``repro.tune.table.disabled()``)
  tuned   : whatever ``repro/tune/tuning_table.json`` resolves for the
            geometry (the shipping dispatch)

``default`` and ``tuned`` are bit-identical by construction — the table
may only hand out tilings that preserve the kernel's k-partition — and
this section asserts exact equality before timing, so a table edit that
changed the bits would fail the benchmark run, not just the gate.  The
grid path is tolerance-equal (its f32 slab accumulation rounds through
different intermediates).

  PYTHONPATH=src python -m benchmarks.tuned_kernel
"""

from __future__ import annotations

import importlib

import jax
import numpy as np

from benchmarks.conv_kernel import _time, darknet_layer_shapes
from repro.core.rebranch import ReBranchSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models import cnn
from repro.tune import table as tune_table

# the package re-exports a jitted op named ``rebranch_conv`` that shadows
# the submodule, so ``import ... as`` would bind the op — go via importlib
_rc = importlib.import_module("repro.kernels.rebranch_conv")

# one geometry per patch-matrix regime the tuner distinguishes:
# l2 = mid 3x3 (gk=2, ragged 64-wide tail), l5 = deep 3x3 (gk=3)
_LAYERS = (2, 5)


def bench_geometry(i: int, c_in: int, c_out: int, k: int, hw: int,
                   repeat: int, key) -> dict[str, float]:
    p = cnn.init_conv(key, k, c_in, c_out, ReBranchSpec())
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, hw, hw, c_in))
    rom, sram = p["rom"], p["sram"]
    args = (rom["w_q"], rom["w_scale"], rom["C"], sram["core"], rom["U"])

    grid = jax.jit(lambda x: _rc.rebranch_conv_pallas(
        x, *args, interpret=True))
    default = jax.jit(lambda x: _rc.rebranch_conv_pallas(x, *args))
    tuned = jax.jit(lambda x: _rc.rebranch_conv_pallas(x, *args))

    # tilings resolve at trace time: warm ``default`` inside the
    # disabled() scope so its trace bakes in the per-kernel defaults
    with tune_table.disabled():
        ref = np.asarray(default(x))
    assert np.array_equal(ref, np.asarray(tuned(x))), (
        f"tuned tiling changed the bits at layer {i} "
        f"(cin={c_in} cout={c_out} k={k} hw={hw})")
    # the interpret grid accumulates through f32 slab copies — same
    # algorithm, not the same ulps, so tolerance-equal only
    np.testing.assert_allclose(ref, np.asarray(grid(x)),
                               rtol=2e-5, atol=2e-5)

    out = {"grid": _time(grid, x, repeat=repeat)}
    with tune_table.disabled():
        out["default"] = _time(default, x, repeat=repeat)
    out["tuned"] = _time(tuned, x, repeat=repeat)
    return out


def run() -> list[str]:
    """benchmarks.run section (gated: see benchmarks.compare).

    Off-TPU the ``grid`` rows time the Pallas interpreter — they are the
    honest "what the seed shipped" baseline, not a TPU grid projection;
    ``default`` vs ``tuned`` isolates what the checked-in table buys on
    the direct lowering.
    """
    key = jax.random.PRNGKey(0)
    shapes = darknet_layer_shapes(32, 6)
    lines = []
    for i in _LAYERS:
        c_in, c_out, k, hw = shapes[i]
        times = bench_geometry(i, c_in, c_out, k, hw, repeat=3,
                               key=jax.random.fold_in(key, i))
        for name, ms in times.items():
            lines.append(f"tuned_kernel_l{i}_{name},{ms * 1e3:.0f},"
                         f"cin={c_in} cout={c_out} k={k} hw={hw}")
    return lines


if __name__ == "__main__":
    enable_compile_cache()
    print("name,us_per_call,derived")
    for line in run():
        print(line, flush=True)
