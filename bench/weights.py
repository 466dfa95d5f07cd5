"""Seeded weights, made on the device in one jitted call.

The benchmark makes every parameter itself, in the tree and the types
the program serves (the program's ``init`` gives only the shapes), so
the plain reference can take the same arrays without taking anything
the program made.  A system adapter supplies the rule that draws one
leaf from its path, its shape and a key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INT8_MAX = 127.0
# trunk weights are drawn as int8 codes of a normal with this many
# standard deviations to the largest code
CODE_SIGMAS = 4.0


def root_key(seed: int):
    """A key from any whole number (the seed may pass 32 bits)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def path_names(path) -> tuple:
    return tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)


def int8_codes(key, shape):
    """int8 codes of a standard normal, ``CODE_SIGMAS`` sigmas to 127."""
    x = jax.random.normal(key, shape) * (INT8_MAX / CODE_SIGMAS)
    return jnp.clip(jnp.round(x), -INT8_MAX, INT8_MAX).astype(jnp.int8)


def code_scale(key, shape, std: float):
    """Per-channel scales that give int8 codes a standard deviation of
    about ``std``, each channel within +-10 % or so."""
    wobble = jnp.exp(0.1 * jax.random.normal(key, shape))
    return (std * CODE_SIGMAS / INT8_MAX * wobble).astype(jnp.float32)


def make(shapes, seed: int, rule):
    """The tree of ``shapes`` (``jax.eval_shape`` of the program's
    init), each leaf drawn by ``rule(names, shape_dtype, key)``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = []
        for (path, sd), k in zip(flat, keys):
            leaf = rule(path_names(path), sd, k)
            if leaf.shape != sd.shape or leaf.dtype != sd.dtype:
                raise ValueError(f"rule drew {leaf.shape} {leaf.dtype} for "
                                 f"{path_names(path)}, the program serves "
                                 f"{sd.shape} {sd.dtype}")
            leaves.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(root_key(seed))
