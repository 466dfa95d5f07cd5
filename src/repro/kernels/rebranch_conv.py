"""Pallas TPU kernel: fused im2col ReBranch convolution (paper §4.1 CNNs).

YOLoC's headline workloads are detection CNNs (VGG-8, ResNet-18,
DarkNet-19, Tiny-YOLO) whose trunk convs live in ROM-CiM.  On TPU a conv
lowers to a matmul over the im2col patch matrix  P [N*OH*OW, KH*KW*C_in],
so the conv kernels here are the conv analogues of cim_matmul /
rebranch_matmul, built on the *same* per-block macro math
(``cim_matmul.cim_block_dot``) — bit-compatible with
``core.cim.cim_conv_model`` in every fidelity mode.

Three entry points:

cim_conv_pallas      : int8 patches x int8 ROM weights through the macro
                       model (ideal / per_subarray / bitserial) — the conv
                       twin of cim_matmul_pallas.
trunk_conv_pallas    : float activations in; per-(patch-row, k-block)
                       dynamic int8 quantisation happens in VMEM, the int8
                       MXU dot and the per-channel scale epilogue follow in
                       the same pass (the 'pallas' TrunkEngine path).
rebranch_conv_pallas : the fused ReBranch conv — trunk kernel plus the
                       per-tap compress sketch on the SAME patch matrix;
                       the tiny epilogue ``out = trunk*w_scale +
                       (t1 @ core) @ U`` is left to XLA.  Key identity:
                       1x1-compress -> KxK core conv composes into one
                       KxK conv, so the trunk's patch matrix serves the
                       branch exactly.  The compress is STRUCTURED: the
                       patch matrix is tap-major (R = taps*C_in), so

                         t1 = (P.reshape(M*taps, C_in) @ C).reshape(M, taps*C_c)

                       is a plain matmul on a zero-copy reshape — branch
                       FLOPs scale with ``taps`` (an earlier version
                       densified the block-diagonal compress as
                       ``P @ kron(I_taps, C)``, paying ``taps^2``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import cim as cim_lib
from repro.core.quant import quant_rows
from repro.kernels.cim_matmul import cim_block_dot, cim_matmul_pallas
from repro.kernels.tiling import (Tiling, conv_index_maps, grid_and_axes,
                                  resolve_direct, resolve_tiling)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _patch_matrix(x: jax.Array, kh: int, kw: int, stride: int, padding: str):
    """im2col + flatten: NHWC -> (P [M, R], (n, oh, ow)), under the
    named scope ``patches``."""
    n = x.shape[0]
    with jax.named_scope("patches"):
        patches, (oh, ow) = cim_lib.im2col(x, kh, kw, stride, padding)
        return patches.reshape(n * oh * ow, patches.shape[-1]), (n, oh, ow)


def _quant_rows(x: jax.Array):
    """In-VMEM dynamic int8 quantisation, per (row, k-block) — the
    reciprocal-form quantiser (pure jnp, safe in a kernel body; see
    core.quant.quant_rows for the bit-identity argument)."""
    return quant_rows(x)


# ---------------------------------------------------------------------------
# int8-in conv: the conv twin of cim_matmul_pallas
# ---------------------------------------------------------------------------

def cim_conv_pallas(
    x_q: jax.Array,                 # int8 [N, H, W, C_in]
    w_q: jax.Array,                 # int8 [KH, KW, C_in, C_out]
    cfg: cim_lib.CiMConfig = cim_lib.DEFAULT_CIM,
    *,
    stride: int = 1,
    padding: str = "SAME",
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    direct: bool | None = None,
) -> jax.Array:
    """Blocked CiM conv; returns f32 [N, OH, OW, C_out] integer-valued
    results, bit-compatible with core.cim.cim_conv_model."""
    kh, kw, c_in, c_out = w_q.shape
    p, (n, oh, ow) = _patch_matrix(x_q, kh, kw, stride, padding)
    # K blocks are clamped to the subarray-aligned patch width inside
    # cim_matmul_pallas, so small-R convs (e.g. a 3x3x3 stem, R=27)
    # don't pad the contraction out to block_k.
    out = cim_matmul_pallas(
        p, w_q.reshape(kh * kw * c_in, c_out), cfg,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret, direct=direct)
    return out.reshape(n, oh, ow, c_out)


# ---------------------------------------------------------------------------
# float-in trunk conv: in-VMEM quantisation + macro dot + scale epilogue
# ---------------------------------------------------------------------------

def _trunk_conv_kernel(cfg, k_axis, x_ref, wq_ref, o_ref):
    @pl.when(pl.program_id(k_axis) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)            # (bm, bk) patch slab
    x_q, scale = _quant_rows(x)
    o_ref[...] += cim_block_dot(cfg, x_q, wq_ref[...]) * scale


def _conv_blocks(m: int, r: int, c_out: int, bm: int, bn: int, bk: int,
                 rows: int):
    """Clamp block sizes to the problem and align K blocks to subarrays."""
    assert bk % rows == 0, "K blocks must hold whole subarrays"
    bk = min(bk, _round_up(r, rows))
    return min(bm, m), min(bn, c_out), bk


def _resolve_conv_tiling(x, w_q, cfg, stride, padding,
                         block_m, block_n, block_k) -> Tiling:
    """Tuning-table tiling for a trunk conv's implied patch GEMM."""
    kh, kw, c_in, c_out = w_q.shape
    _, oh = cim_lib.conv_pads(x.shape[1], kh, stride, padding)
    _, ow = cim_lib.conv_pads(x.shape[2], kw, stride, padding)
    return resolve_tiling(
        "trunk_conv", cfg.mode, str(x.dtype),
        x.shape[0] * oh * ow, kh * kw * c_in, c_out,
        block_m=block_m, block_n=block_n, block_k=block_k,
        defaults=(128, 128, 512), rows=cfg.rows_per_subarray)


def _trunk_patch_dot(p, w2d, cfg, t: Tiling, interpret):
    """Blocked Pallas trunk pass over the flat patch matrix.

    p [M, R] float patches, w2d [R, C_out] int8 — returns the UNscaled f32
    trunk accumulation [M, C_out] (callers apply ``w_scale``).  K blocks
    stay subarray-aligned so the macro fidelity model sees the same row
    grouping as the unblocked oracle.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, r = p.shape
    c_out = w2d.shape[1]
    bm, bn, bk = _conv_blocks(m, r, c_out, t.block_m, t.block_n, t.block_k,
                              cfg.rows_per_subarray)
    pad_m, pad_n, pad_k = (-m) % bm, (-c_out) % bn, (-r) % bk
    pp = jnp.pad(p, ((0, pad_m), (0, pad_k)))
    wp = jnp.pad(w2d, ((0, pad_k), (0, pad_n)))
    gm, gn, gk = pp.shape[0] // bm, wp.shape[1] // bn, pp.shape[1] // bk
    grid, _, _, k_axis = grid_and_axes(gm, gn, gk, t.dim_order)
    x_map, w_map, o_map = conv_index_maps(t.dim_order)

    out = pl.pallas_call(
        functools.partial(_trunk_conv_kernel, cfg, k_axis),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), x_map),
            pl.BlockSpec((bk, bn), w_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((pp.shape[0], wp.shape[1]),
                                       jnp.float32),
        interpret=interpret,
    )(pp, wp)
    return out[:m, :c_out]


# ---------------------------------------------------------------------------
# direct (plain-XLA) trunk lowering — the off-TPU fast path
# ---------------------------------------------------------------------------

def _stacked_patches(x, kh, kw, stride, padding):
    """Tap-major patch matrix via stacked strided slices.

    Produces exactly the same P [M, taps*C_in] as :func:`_patch_matrix`
    (tap-major layout), but through kh*kw strided views + one stack —
    much cheaper for XLA:CPU than the gather-based im2col.  Under the
    named scope ``patches``.
    """
    n, h, w, c_in = x.shape
    (ph0, ph1), oh = cim_lib.conv_pads(h, kh, stride, padding)
    (pw0, pw1), ow = cim_lib.conv_pads(w, kw, stride, padding)
    hi = (oh - 1) * stride + 1
    wi = (ow - 1) * stride + 1
    with jax.named_scope("patches"):
        xp = jnp.pad(x, ((0, 0), (ph0, ph1), (pw0, pw1), (0, 0)))
        cols = [xp[:, i:i + hi:stride, j:j + wi:stride, :]
                for i in range(kh) for j in range(kw)]
        p = jnp.stack(cols, axis=3).reshape(n * oh * ow, kh * kw * c_in)
    return p, (n, oh, ow), ((ph0, ph1), (pw0, pw1), hi, wi)


def _block_absmaxes(x, p, kh, kw, c_in, stride, pads, bk):
    """Per-(patch-row, k-block) absolute maxima, without widening P.

    Returns ([(k0, k1)], [absmax (M, 1)]) matching the kernel's k-block
    partition.  The maxima are assembled from per-pixel channel maxima
    via shifted-window max reductions when block boundaries allow
    (separable for gk == 1; per-tap windows when blocks hold whole
    taps); the general ragged case falls back to column maxima over P.
    Zero conv padding never raises a max, so all three routes compute
    the exact same numbers the kernel sees in its padded (bm, bk) slab.
    """
    (ph0, ph1), (pw0, pw1), hi, wi = pads
    taps = kh * kw
    m, r = p.shape
    gk = -(-r // bk)
    if gk == 1:
        am = jnp.pad(jnp.max(jnp.abs(x), axis=-1),
                     ((0, 0), (ph0, ph1), (pw0, pw1)))
        mw = am[:, :, 0:wi:stride]
        for j in range(1, kw):
            mw = jnp.maximum(mw, am[:, :, j:j + wi:stride])
        mh = mw[:, 0:hi:stride]
        for i in range(1, kh):
            mh = jnp.maximum(mh, mw[:, i:i + hi:stride])
        return [(0, r)], [mh.reshape(m, 1)]
    if bk % c_in == 0:
        # block boundaries fall on tap boundaries: per-tap channel-max
        # windows, then a max over each block's taps
        am = jnp.pad(jnp.max(jnp.abs(x), axis=-1),
                     ((0, 0), (ph0, ph1), (pw0, pw1)))
        amt = [am[:, i:i + hi:stride, j:j + wi:stride]
               for i in range(kh) for j in range(kw)]
        tpb = bk // c_in
        bounds, absmaxes = [], []
        for b in range(gk):
            t0, t1 = b * tpb, min((b + 1) * tpb, taps)
            blk = amt[t0]
            for t in range(t0 + 1, t1):
                blk = jnp.maximum(blk, amt[t])
            bounds.append((t0 * c_in, min(t1 * c_in, r)))
            absmaxes.append(blk.reshape(m, 1))
        return bounds, absmaxes
    bounds, absmaxes = [], []
    for b in range(gk):
        k0, k1 = b * bk, min((b + 1) * bk, r)
        bounds.append((k0, k1))
        absmaxes.append(jnp.max(jnp.abs(p[:, k0:k1]), axis=1, keepdims=True))
    return bounds, absmaxes


def _direct_trunk_patch_dot(p, bounds, absmaxes, w2d, cfg):
    """Direct lowering of ``_trunk_patch_dot``'s block accumulation.

    Per k-block: the same reciprocal quantisation the kernel applies in
    VMEM, the same macro math (f32 GEMM in ideal mode — exact, block
    dots stay under 2**24 — ``cim_block_dot`` otherwise), accumulated in
    the same ascending-K order.  Ragged tails padded with zero rows read
    as 0 through every ADC path, matching the kernel's padded slabs.

    The multi-block accumulate runs under ``lax.scan``, NOT an unrolled
    add chain: an open ``acc + dot*scale`` elementwise graph is fused by
    XLA with whatever the caller puts next, and the FMA contraction LLVM
    then applies depends on that consumer — the same conv would round
    differently eagerly vs under a caller's jit, breaking the eager/jit
    bit-parity the sharded engine contracts (``optimization_barrier``
    does NOT help: XLA's CPU pipeline drops it before fusion).  A scan
    body is compiled as a while-loop body — its own fusion domain,
    bit-identical in every calling context, the same boundary the
    interpret-mode ``pallas_call`` grid enjoys.

    The per-block ``dot * scale`` parts are computed OUTSIDE the scan on
    ragged static slices and only the adds run inside it: a 64-column
    tail block costs a 64-wide GEMM instead of being zero-padded out to
    ``bk`` (78% wasted MACs on a 576-wide DarkNet-19 patch row).  This
    is value-exact (padded columns quantise to 0 and contribute exact-0
    dot terms; ``adc(0) == 0`` on every fidelity path) and bit-stable:
    a lone mul cannot be FMA-contracted — only the adds can, and those
    stay behind the scan boundary.
    """
    m, r = p.shape
    n = w2d.shape[1]
    gk = len(bounds)
    rows = cfg.rows_per_subarray
    w2f = w2d.astype(jnp.float32)

    def block_part(k0, k1, absmax):
        # reciprocal form throughout — matches quant_rows bit-for-bit
        # (jitted XLA turns /127 into *(1/127) anyway; see core.quant)
        pb = p[:, k0:k1]
        scale = jnp.maximum(absmax, 1e-8) * (1.0 / 127.0)
        if cfg.mode == "ideal":
            return (jnp.round(pb * (1.0 / scale)) @ w2f[k0:k1]) * scale
        q = jnp.clip(jnp.round(pb * (1.0 / scale)),
                     -127.0, 127.0).astype(jnp.int8)
        pad = _round_up(k1 - k0, rows) - (k1 - k0)
        return cim_block_dot(cfg, jnp.pad(q, ((0, 0), (0, pad))),
                             jnp.pad(w2d[k0:k1], ((0, pad), (0, 0)))) * scale

    if gk == 1:
        # single block — no cross-block accumulate to protect (the lone
        # dot*scale's downstream adds all carry exact-zero or post-mul
        # addends, where FMA contraction is value-exact)
        (k0, k1), = bounds
        return block_part(k0, k1, absmaxes[0])
    parts = jnp.stack([block_part(k0, k1, am)
                       for (k0, k1), am in zip(bounds, absmaxes)])
    out, _ = jax.lax.scan(lambda acc, pt: (acc + pt, None),
                          jnp.zeros((m, n), jnp.float32), parts)
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "block_k", "stride",
                                             "padding"))
def _direct_trunk_conv(x, w_q, *, cfg, block_k, stride, padding):
    """Direct trunk conv; returns (unscaled trunk [M, C_out], P).

    Jitted as its own compilation unit so eager callers dispatch one
    executable; the bits are identical whether the caller is eager,
    jitted, or a shard_map body — the sharded trunk's bit-parity
    contract depends on this.  The jit alone does not provide that (an
    outer jit inlines it); the scan inside
    :func:`_direct_trunk_patch_dot` does.
    """
    kh, kw, c_in, c_out = w_q.shape
    rows = cfg.rows_per_subarray
    r = kh * kw * c_in
    bk = min(block_k, _round_up(r, rows))
    xf = x.astype(jnp.float32)     # the grid kernel quantises f32 slabs
    p, _, pads = _stacked_patches(xf, kh, kw, stride, padding)
    bounds, absmaxes = _block_absmaxes(xf, p, kh, kw, c_in, stride, pads, bk)
    out = _direct_trunk_patch_dot(p, bounds, absmaxes,
                                  w_q.reshape(r, c_out), cfg)
    return out, p


def trunk_conv_pallas(
    x: jax.Array,                   # [N, H, W, C_in] float
    w_q: jax.Array,                 # int8 [KH, KW, C_in, C_out] (ROM)
    w_scale: jax.Array,             # per-output-channel f32
    cfg: cim_lib.CiMConfig = cim_lib.CiMConfig(mode="ideal"),
    *,
    stride: int = 1,
    padding: str = "SAME",
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    direct: bool | None = None,
) -> jax.Array:
    """Frozen-trunk convolution, quantisation fused into the macro pass.

    Block sizes left as ``None`` come from the tuning table
    (``repro.tune``), keyed on this conv's implied patch-GEMM geometry.
    Off-TPU the trunk lowers directly to blocked XLA GEMMs replicating
    the grid kernel's decomposition (``direct``/``interpret`` override).
    """
    kh, kw, c_in, c_out = w_q.shape
    _, oh = cim_lib.conv_pads(x.shape[1], kh, stride, padding)
    _, ow = cim_lib.conv_pads(x.shape[2], kw, stride, padding)
    if x.shape[0] * oh * ow == 0:
        return jnp.zeros((x.shape[0], oh, ow, c_out), x.dtype)
    t = _resolve_conv_tiling(x, w_q, cfg, stride, padding,
                             block_m, block_n, block_k)
    if resolve_direct(interpret, direct, t):
        n = x.shape[0]
        out, _ = _direct_trunk_conv(x, w_q, cfg=cfg, block_k=t.block_k,
                                    stride=stride, padding=padding)
    else:
        p, (n, oh, ow) = _patch_matrix(x, kh, kw, stride, padding)
        out = _trunk_patch_dot(p, w_q.reshape(-1, c_out), cfg, t, interpret)
    out = out * w_scale.reshape(1, -1).astype(jnp.float32)
    return out.reshape(n, oh, ow, c_out).astype(x.dtype)


# ---------------------------------------------------------------------------
# fused ReBranch conv: trunk + structured compress on the shared patches
# ---------------------------------------------------------------------------

def structured_compress(p: jax.Array, c2d: jax.Array, taps: int) -> jax.Array:
    """Per-tap compress sketch of a tap-major patch matrix.

    p [M, taps*C_in] -> t1 [M, taps*C_c] with  t1[m, t*C_c+j] =
    P[m, t*C_in:(t+1)*C_in] @ C[:, j].  The patch matrix is tap-major, so
    the per-tap dot is a plain matmul on a ZERO-COPY reshape — FLOPs are
    M * taps * C_in * C_c, scaling with ``taps`` (the dense
    ``P @ kron(I_taps, C)`` form costs taps^2).  (Folding the compress
    and core into one ``P @ (blkdiag(C) @ core_flat)`` GEMM is
    mathematically equivalent and looks cheaper on paper, but measures
    slower end to end on CPU: the wide folded GEMM forces a second
    288-wide streaming read of P, while this skinny leg stays hot in
    cache behind the trunk dot.)
    """
    m = p.shape[0]
    c_in, c_c = c2d.shape
    t1 = jax.lax.dot_general(
        p.reshape(m * taps, c_in).astype(jnp.float32),
        c2d.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return t1.reshape(m, taps * c_c)


def rebranch_conv_pallas(
    x: jax.Array,                   # [N, H, W, C_in] float
    w_q: jax.Array,                 # int8 [KH, KW, C_in, C_out] trunk (ROM)
    w_scale: jax.Array,             # per-output-channel f32
    c: jax.Array,                   # [1, 1, C_in, C_c] fixed compress (ROM)
    core: jax.Array,                # [KH, KW, C_c, C_u] trainable (SRAM)
    u: jax.Array,                   # [1, 1, C_u, C_out] fixed decompress (ROM)
    cfg: cim_lib.CiMConfig = cim_lib.CiMConfig(mode="ideal"),
    *,
    stride: int = 1,
    padding: str = "SAME",
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    direct: bool | None = None,
) -> jax.Array:
    """Fused ReBranch convolution forward (beyond-paper fast path).

    The 1x1-compress -> KxK-core branch composes into one KxK conv, so
    the trunk dot and the compress sketch share ONE im2col patch matrix:
      trunk[m, n] += macro(quant_blk(P), w_q) * scale_blk   (Pallas grid)
      t1          = structured_compress(P, C)               (MXU matmul)
      out         = trunk * w_scale + (t1 @ core_flat) @ U  (tiny epilogue)

    The compress is the per-tap structured dot (see
    :func:`structured_compress`): branch sketch FLOPs scale with ``taps``,
    not ``taps^2`` as the old ``kron(I_taps, C)`` densification did.  It
    runs as a plain XLA matmul on a zero-copy reshape of the patch matrix
    rather than inside the macro grid: the trunk grid re-reads each patch
    block once per output-channel block anyway, so the one extra read is
    noise, and XLA overlaps the small sketch dot with the trunk kernel.
    """
    kh, kw, c_in, c_out = w_q.shape
    assert core.shape[:2] == (kh, kw), (core.shape, w_q.shape)
    c_c, c_u = core.shape[2], core.shape[3]

    _, oh = cim_lib.conv_pads(x.shape[1], kh, stride, padding)
    _, ow = cim_lib.conv_pads(x.shape[2], kw, stride, padding)
    if x.shape[0] * oh * ow == 0:
        return jnp.zeros((x.shape[0], oh, ow, c_out), x.dtype)
    t = _resolve_conv_tiling(x, w_q, cfg, stride, padding,
                             block_m, block_n, block_k)
    if resolve_direct(interpret, direct, t):
        # trunk and branch share the stacked patch matrix
        n = x.shape[0]
        trunk, p = _direct_trunk_conv(x, w_q, cfg=cfg, block_k=t.block_k,
                                      stride=stride, padding=padding)
    else:
        p, (n, oh, ow) = _patch_matrix(x, kh, kw, stride, padding)
        trunk = _trunk_patch_dot(p, w_q.reshape(-1, c_out), cfg, t, interpret)
    out = trunk * w_scale.reshape(1, -1).astype(jnp.float32)
    t1 = structured_compress(p, c.reshape(c_in, c_c), kh * kw)
    branch = (t1 @ core.reshape(kh * kw * c_c, c_u).astype(jnp.float32)
              ) @ u.reshape(c_u, c_out).astype(jnp.float32)
    return (out + branch).reshape(n, oh, ow, c_out).astype(x.dtype)
