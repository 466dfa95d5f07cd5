"""Plain reference of a ReBranch DarkNet-style detector (YOLOv2 backbone).

Written from the paper's description (YOLoC §3.2, Fig. 7-8, Table I)
in straightforward ``jax.numpy``; it imports nothing of the program.
Every ROM conv site computes

    y = Trunk(x) + Decompress(Core(Compress(x)))
    Trunk(x) = (P_q @ W_q) * s_row * w_scale      (int8 x int8, exact)

where P is the site's im2col patch matrix (one row per output pixel),
quantised per row to int8 (symmetric, absmax / 127), and W_q the int8
ROM weights with per-output-channel scales; the branch is a 1x1
compress, the KxK core, and a 1x1 decompress, in float32.  Then the
inference batch norm (frozen statistics, eps 1e-5) and a leaky ReLU
(slope 0.1).  "M" is a 2x2 max pool of stride 2; the detector ends in
a plain 1x1 float conv to ``anchors * (5 + classes)`` channels.

Float work runs at ``highest`` matmul precision.  ``trunk_bits=4`` is
the control: the trunk in int4 (weights and activations), the nearest
precision below the int8 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BN_EPS = 1e-5
LEAKY = 0.1


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _quant(x, axis, bits):
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-8) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax), scale


def trunk(x, w_q, w_scale, bits):
    """The int8 ROM trunk conv (SAME, stride 1) through the patch
    matrix; ``bits=4`` requantises weights and activations to int4."""
    k, _, c_in, c_out = w_q.shape
    n, h, w, _ = x.shape
    # patches ordered (c_in, kh, kw) -> weights reordered to match
    p = jax.lax.conv_general_dilated_patches(
        x, (k, k), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    p = p.reshape(n * h * w, c_in * k * k)
    wm = jnp.transpose(w_q, (2, 0, 1, 3)).reshape(c_in * k * k, c_out)
    scale = w_scale.reshape(1, c_out)
    if bits == 8:
        codes = wm.astype(jnp.int8)
    else:
        codes, s4 = _quant(wm.astype(jnp.float32) * scale, 0, bits)
        codes, scale = codes.astype(jnp.int8), s4
    p_q, s_row = _quant(p, -1, bits)
    acc = jax.lax.dot_general(p_q.astype(jnp.int8), codes,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * s_row * scale
    return y.reshape(n, h, w, c_out)


def site(p, bn, x, bits):
    rom, core = p["rom"], p["sram"]["core"]
    y = trunk(x, rom["w_q"], rom["w_scale"], bits)
    y = y + _conv(_conv(_conv(x, rom["C"]), core), rom["U"])
    s = bn["sram"]
    y = (y - s["mean"]) * jax.lax.rsqrt(s["var"] + BN_EPS) * s["scale"] \
        + s["bias"]
    return jnp.where(y >= 0, y, LEAKY * y)


@functools.partial(jax.jit, static_argnames=("plan", "anchors", "bits"))
def _forward(params, images, plan, anchors, bits):
    x, i = images.astype(jnp.float32), 0
    for item in plan:
        if item == "M":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        else:
            x = site(params["convs"][i], params["bns"][i], x, bits)
            i += 1
    for blk in params["head"]:
        x = site(blk["conv"], blk["bn"], x, bits)
    x = _conv(x, params["pred"]["sram"]["w"])
    b, h, w, c = x.shape
    return x.reshape(b, h, w, anchors, c // anchors)


def forward(params, images, body: dict, trunk_bits: int = 8):
    """Detector output [N, S, S, anchors, 5 + classes] for ``images``
    [N, H, W, 3], with the configuration ``body`` (its ``backbone``)."""
    plan = tuple("M" if it == "M" else tuple(it) for it in body["backbone"])
    return _forward(params, images, plan, body["head_anchors"], trunk_bits)
