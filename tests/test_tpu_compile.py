"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The TPU compiler compiles for a described chip that is not attached, so
these run on any host where ``libtpu`` is installed: each test lowers a
kernel at a real DarkNet-19 / LM width with ``interpret=False`` and asserts
that the compiled program holds the Mosaic kernel (``tpu_custom_call``).
They catch what interpret mode cannot: unaligned block slices, VMEM
overruns, kernels the chip's compiler refuses.  Nothing runs, so they say
nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import cim as cim_lib
from repro.kernels.cim_matmul import cim_matmul_pallas
from repro.kernels.rebranch_conv import (rebranch_conv_pallas,
                                         trunk_conv_pallas)

IDEAL = cim_lib.CiMConfig(mode="ideal")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("mode,m,k,n", [
    ("bitserial", 256, 1152, 256),
    ("ideal", 8, 4096, 4096),
])
def test_cim_matmul_compiles_to_kernel(one_chip, mode, m, k, n):
    fn = functools.partial(cim_matmul_pallas,
                           cfg=cim_lib.CiMConfig(mode=mode), interpret=False)
    text = _compiled_text(fn, one_chip, ((m, k), jnp.int8),
                          ((k, n), jnp.int8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("hw,c_in,c_out", [
    (416, 3, 32),          # DarkNet-19 416 px stem
    (52, 128, 256),        # DarkNet-19 3x3 conv at 52x52
])
def test_trunk_conv_compiles_to_kernel(one_chip, hw, c_in, c_out):
    fn = functools.partial(trunk_conv_pallas, cfg=IDEAL, interpret=False)
    text = _compiled_text(fn, one_chip,
                          ((8, hw, hw, c_in), jnp.float32),
                          ((3, 3, c_in, c_out), jnp.int8),
                          ((c_out,), jnp.float32))
    assert "tpu_custom_call" in text


def test_fused_rebranch_conv_compiles_to_kernel(one_chip):
    """One DarkNet-19 416 px site (3x3 128->256 at 52x52) with its
    D=U=4 branch: compress C, trainable core, decompress U."""
    hw, c_in, c_out = 52, 128, 256
    c_c, c_u = c_in // 4, c_out // 4
    fn = functools.partial(rebranch_conv_pallas, cfg=IDEAL, interpret=False)
    text = _compiled_text(fn, one_chip,
                          ((8, hw, hw, c_in), jnp.float32),
                          ((3, 3, c_in, c_out), jnp.int8),
                          ((c_out,), jnp.float32),
                          ((1, 1, c_in, c_c), jnp.float32),
                          ((3, 3, c_c, c_u), jnp.float32),
                          ((1, 1, c_u, c_out), jnp.float32))
    assert "tpu_custom_call" in text
