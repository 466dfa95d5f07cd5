"""Halo-exchange sharded convolution: the Pallas conv kernels under
``shard_map`` on NHWC inputs sharded over H.

YOLoC's trunks are *fixed* ROM arrays — scaling serving past one chip
means partitioning the activations, not the weights ("WWW": where to
compute; "Breaking Barriers": array utilisation is the limiter once CiM
fabrics scale out).  A KxK conv's receptive field leaks ``kh-1`` rows
across a spatial cut, so instead of replicating the feature map every
device exchanges only that halo with its neighbours
(``jax.lax.ppermute``) and runs the ordinary fused im2col kernel on its
extended slab.  Wire volume per conv: ``halo_rows * W * C`` per device
pair, vs the full ``H * W * C`` an all-gather would move.

Bit-parity contract: per-device TRUNK results are **bit-identical** to
the unsharded ``trunk_conv_pallas``.  This holds because every per-row
quantity (dynamic int8 quantisation scale, k-block accumulation order,
scale epilogue) depends only on that patch row's values and the
K-blocking — both of which the halo exchange preserves exactly — and the
trunk's f32 accumulators only ever hold exactly-representable integer
partial sums, immune to reduction reassociation.  Missing neighbours
contribute zeros through ``ppermute``, which is precisely the conv's own
SAME zero padding.  The fused ReBranch path matches its unsharded twin
to 1 ulp rather than bitwise: the branch sketch is a genuine float GEMM,
and BLAS reduction order is shape-dependent (local M != global M).

Two geometries, chosen statically by :func:`plan_halo`:

aligned : ``padding='SAME'`` and ``H % (n * stride) == 0`` — shard
          boundaries coincide with output ownership; two-sided halo
          (``ph0`` rows down, ``kh - stride - ph0`` rows up), nothing
          repadded, only halo rows ever cross the wire.  kh=1 convs
          exchange nothing at all (the no-halo fast path).
general : any other H/stride/padding (odd H, VALID, uneven shards) —
          the global top padding plus alignment rows are materialised
          once so every shard starts exactly at its first output row's
          receptive field; the (<= kh - stride)-row bottom halo still
          moves by ``ppermute``.  Surplus output rows are sliced off
          after the shard_map.

``plan_halo`` returns None when a halo would span more than one
neighbour shard (H too small for the mesh); callers fall back to the
unsharded kernel — still correct, just not sharded.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import cim as cim_lib
from repro.core.cim import conv_pads
from repro.core.rebranch import trunk_conv_residuals, trunk_conv_ste_bwd
from repro.kernels.rebranch_conv import (
    rebranch_conv_pallas, trunk_conv_pallas,
)

@dataclasses.dataclass(frozen=True)
class HaloPlan:
    """Static geometry of one H-sharded conv (all fields trace-static).

    top/bot: halo rows received from the previous/next device on the
        mesh axis (the buffers ``ppermute`` moves; edge devices receive
        zeros, which is the conv's own zero padding).
    pad_top/pad_bot: zero rows materialised globally before the
        shard_map (general path only; 0/0 on the aligned path).
    oh: true output rows; ol: output rows computed per device
        (``n * ol > oh`` means the tail rows are sliced off afterwards).
    """
    n: int
    aligned: bool
    top: int
    bot: int
    pad_top: int
    pad_bot: int
    oh: int
    ol: int


def plan_halo(h: int, kh: int, stride: int, padding: str,
              n: int) -> HaloPlan | None:
    """Halo geometry for H rows / KHxK kernel sharded n ways, or None when
    a halo would span more than one neighbour shard (fall back unsharded).
    """
    (ph0, _), oh = conv_pads(h, kh, stride, padding)
    if padding == "SAME" and h % (n * stride) == 0:
        hl = h // n
        top, bot = ph0, max(kh - stride - ph0, 0)
        if max(top, bot) > hl:
            return None
        return HaloPlan(n=n, aligned=True, top=top, bot=bot,
                        pad_top=0, pad_bot=0, oh=oh, ol=oh // n)
    # general path: ol covers both the outputs (ceil(oh/n)) and the
    # materialised input rows (ceil((ph0+h)/(n*stride))) so no real row is
    # ever truncated into the zero-filled edge halo
    ol = max(-(-oh // n), -(-(ph0 + h) // (n * stride)))
    bot = max(kh - stride, 0)
    if bot > ol * stride:
        return None
    return HaloPlan(n=n, aligned=False, top=0, bot=bot,
                    pad_top=ph0, pad_bot=n * ol * stride - ph0 - h,
                    oh=oh, ol=ol)


def halo_bytes(x_shape, kh: int, stride: int, padding: str, n: int,
               dtype_bytes: int = 4) -> int:
    """Wire bytes one conv's halo exchange moves per device pair — the
    analytic cross-check for the dryrun's collective-permute accounting."""
    plan = plan_halo(x_shape[1], kh, stride, padding, n)
    if plan is None or plan.n <= 1:
        return 0
    rows = plan.top + plan.bot
    return rows * x_shape[0] * x_shape[2] * x_shape[3] * dtype_bytes


def _exchange(x, plan: HaloPlan, axis: str):
    """Assemble the extended local slab: [top halo; shard; bottom halo].

    ``ppermute`` fills non-receiving edge devices with zeros — exactly the
    zero rows SAME padding (aligned path) or the sliced-off tail (general
    path) would contribute, so no edge special-casing is needed.
    """
    parts = []
    if plan.top:
        parts.append(jax.lax.ppermute(
            x[:, -plan.top:], axis,
            [(i, i + 1) for i in range(plan.n - 1)]))
    parts.append(x)
    if plan.bot:
        parts.append(jax.lax.ppermute(
            x[:, :plan.bot], axis,
            [(i + 1, i) for i in range(plan.n - 1)]))
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else x


def _prepare(x, kh: int, kw: int, stride: int, padding: str, n: int):
    """Shared pre-shard_map geometry: plan + global W (and general-path H)
    zero padding, so the per-shard kernel always runs padding='VALID'."""
    plan = plan_halo(x.shape[1], kh, stride, padding, n)
    if plan is None:
        return None, x
    (pw0, pw1), _ = conv_pads(x.shape[2], kw, stride, padding)
    x = jnp.pad(x, ((0, 0), (plan.pad_top, plan.pad_bot),
                    (pw0, pw1), (0, 0)))
    return plan, x


def _finish(out, plan: HaloPlan):
    return out if out.shape[1] == plan.oh else out[:, :plan.oh]


# ---------------------------------------------------------------------------
# trunk conv (the 'pallas_sharded' engine's conv path) + STE backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def sharded_trunk_conv(cfg: cim_lib.CiMConfig, stride: int, padding: str,
                       mesh, axis: str, x, w_q, w_scale):
    """H-sharded frozen-trunk convolution, bit-identical to the unsharded
    ``trunk_conv_pallas``; STE backward (dx only — the ROM cannot be
    written) via the plain XLA conv transpose, which GSPMD shards.

    mesh/axis are static: the jax Mesh and the name of its axis H is
    sharded over.  Raises when :func:`plan_halo` is infeasible — callers
    (the engine) check feasibility first and fall back unsharded.
    """
    plan, xp = _prepare(x, w_q.shape[0], w_q.shape[1], stride, padding,
                        mesh.shape[axis])
    if plan is None:
        raise ValueError(
            f"halo plan infeasible: H={x.shape[1]} kernel={w_q.shape[0]} "
            f"stride={stride} over {mesh.shape[axis]} shards (halo spans "
            f"more than one neighbour); use the unsharded engine")

    def body(xl, w_q, w_scale):
        xe = _exchange(xl, plan, axis)
        return trunk_conv_pallas(xe, w_q, w_scale, cfg,
                                 stride=stride, padding="VALID")

    spec = P(None, axis, None, None)
    out = jax.shard_map(body, mesh=mesh, in_specs=(spec, P(), P()),
                        out_specs=spec, check_vma=False)(xp, w_q, w_scale)
    return _finish(out, plan)


def _sharded_fwd(cfg, stride, padding, mesh, axis, x, w_q, w_scale):
    out = sharded_trunk_conv(cfg, stride, padding, mesh, axis,
                             x, w_q, w_scale)
    return out, trunk_conv_residuals(x, w_q, w_scale)


def _sharded_bwd(cfg, stride, padding, mesh, axis, res, g):
    del cfg, mesh, axis
    return trunk_conv_ste_bwd(stride, padding, res, g)


sharded_trunk_conv.defvjp(_sharded_fwd, _sharded_bwd)


# ---------------------------------------------------------------------------
# fused ReBranch conv (inference fast path), same halo geometry
# ---------------------------------------------------------------------------

def sharded_rebranch_conv(x, w_q, w_scale, c, core, u,
                          cfg: cim_lib.CiMConfig = cim_lib.CiMConfig(
                              mode="ideal"),
                          *, stride: int = 1, padding: str = "SAME",
                          mesh=None, axis: str = "data", tiling=None):
    """H-sharded fused ReBranch conv (trunk + compress sketch in one pass
    per shard).  The branch epilogue ``(t1 @ core) @ U`` is per-patch-row,
    so it shards for free with the output rows.  Trunk contribution is
    bit-identical to ``rebranch_conv_pallas``; the float branch GEMMs
    match to 1 ulp (see the module docstring).  Forward-only, like its
    unsharded twin.

    ``tiling`` (a ``repro.tune.Tiling``) pins the per-shard kernel's
    block sizes; left ``None``, each shard consults the tuning table
    keyed on its *local* patch-GEMM geometry.  Either way bit-parity is
    safe: legal tilings never change the trunk's k-partition, so a
    sharded lookup (local M) and an unsharded one (global M) landing on
    different entries still produce bit-identical trunks."""
    plan, xp = _prepare(x, w_q.shape[0], w_q.shape[1], stride, padding,
                        mesh.shape[axis])
    if plan is None:
        raise ValueError(
            f"halo plan infeasible: H={x.shape[1]} kernel={w_q.shape[0]} "
            f"stride={stride} over {mesh.shape[axis]} shards")
    bm, bn, bk = ((tiling.block_m, tiling.block_n, tiling.block_k)
                  if tiling is not None else (None, None, None))

    def body(xl, w_q, w_scale, c, core, u):
        xe = _exchange(xl, plan, axis)
        return rebranch_conv_pallas(xe, w_q, w_scale, c, core, u, cfg,
                                    stride=stride, padding="VALID",
                                    block_m=bm, block_n=bn, block_k=bk)

    spec = P(None, axis, None, None)
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(spec, P(), P(), P(), P(), P()),
                        out_specs=spec, check_vma=False)(
                            xp, w_q, w_scale, c, core, u)
    return _finish(out, plan)
