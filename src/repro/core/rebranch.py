"""ReBranch (paper §3.2, Fig. 7): frozen ROM trunk + small trainable branch.

    y = Trunk_ROM(x) + Decompress(ResCore(Compress(x))) (+ bias)

* Trunk: int8 weights + per-channel scales, physically immutable ("ROM").
* Compress ``C``  (d_in  -> d_in//D)  : fixed point-wise projection (ROM).
* ResCore ``core``(d_in//D -> d_out//U): the ONLY trainable tensor ("SRAM").
* Decompress ``U``(d_out//U -> d_out) : fixed point-wise projection (ROM).

With the paper's optimum D=U=4 the branch holds 1/16 of the trunk's
parameters (Fig. 11).  ``core`` is zero-initialised so a freshly-frozen
model is exactly the pretrained model (branch contributes 0).

Parameter convention: every pytree whose dict key is ``"rom"`` is frozen —
excluded from autodiff, optimizer state, gradient collectives and
checkpoints.  ``partition``/``combine`` implement that split.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cim as cim_lib
from repro.core import quant

ROM_KEY = "rom"
# A site's branch prepared for serving (``serving_branch``): derived, never
# trainable, checkpointed or swapped.
SERVED_KEY = "served"


@dataclasses.dataclass(frozen=True)
class ReBranchSpec:
    d_ratio: int = 4                 # compression ratio D (paper Fig. 11)
    u_ratio: int = 4                 # decompression ratio U
    enabled: bool = True             # False -> plain trainable linear ("SRAM")
    # Trunk execution backend: any name in the repro.engine registry
    # ('int8_native' | 'dequant' | 'pallas' out of the box).  Resolution
    # is strict — unknown names raise with the registered set.
    trunk_impl: str = "int8_native"
    cim: cim_lib.CiMConfig = dataclasses.field(
        default_factory=lambda: cim_lib.CiMConfig(mode="ideal"))
    param_dtype: Any = jnp.float32   # branch/scale dtype
    branch_enabled: bool = True      # trunk-only (frozen, no adapter) if False
    # Speculative-draft mode: skip the ROM trunk matmul entirely and run
    # only the SRAM-resident branch (y = (x@C)@(core@U) + b).  The output
    # approximates the full layer at ~1/compression of the FLOPs — the
    # draft half of draft/verify speculative decoding (serve spec mode).
    # Never used for training or verified serving output.
    trunk_skip: bool = False

    @property
    def compression(self) -> int:
        return self.d_ratio * self.u_ratio


# ---------------------------------------------------------------------------
# pytree partitioning: ROM (frozen) vs SRAM (trainable)
# ---------------------------------------------------------------------------

def _is_none(x) -> bool:
    return x is None


def partition(params):
    """Split params into (trainable, frozen) trees; non-members are None."""
    def walk(node, in_rom):
        if isinstance(node, dict):
            train, froz = {}, {}
            for k, v in node.items():
                t, f = walk(v, in_rom or k == ROM_KEY)
                train[k], froz[k] = t, f
            return train, froz
        if isinstance(node, (list, tuple)):
            typ = type(node)
            if typ in (list, tuple):
                pairs = [walk(v, in_rom) for v in node]
                return typ(p[0] for p in pairs), typ(p[1] for p in pairs)
            if hasattr(node, "_fields"):          # namedtuple
                pairs = [walk(v, in_rom) for v in node]
                return (typ(*(p[0] for p in pairs)),
                        typ(*(p[1] for p in pairs)))
            # other tuple subclasses (e.g. jax.sharding.PartitionSpec) are
            # pytree LEAVES in jax.tree semantics — do not recurse/rebuild
        return (None, node) if in_rom else (node, None)

    return walk(params, False)


def combine(trainable, frozen):
    """Inverse of :func:`partition`."""
    return jax.tree.map(
        lambda a, b: a if a is not None else b,
        trainable, frozen, is_leaf=_is_none)


def trainable_count(params) -> int:
    t, _ = partition(params)
    return sum(x.size for x in jax.tree.leaves(t))


def frozen_count(params) -> int:
    _, f = partition(params)
    return sum(x.size for x in jax.tree.leaves(f))


# ---------------------------------------------------------------------------
# Trunk matmul: frozen int8 path with a straight-through backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def trunk_matmul(cfg: cim_lib.CiMConfig, out_axes, x, w_q, w_scale):
    """y = CiM(quantize(x), w_q) * (sx * w_scale);  frozen-weight matmul.

    Forward runs the (possibly non-ideal) CiM model on int8 operands;
    backward is the straight-through estimator  dx = g @ dequant(w)^T.
    No dW is ever produced (the ROM cannot be written).

    out_axes (static, optional): logical sharding annotation placed on the
    RAW dot output (and on dx in the backward) so the SPMD partitioner can
    turn row-parallel partial-sum all-reduces into reduce-scatters.
    """
    x_q, sx = quant.quantize_activations(x)
    out = cim_lib.cim_matmul_model(x_q, w_q, cfg)
    if out_axes is not None:
        from repro.distributed.sharding import shard
        out = shard(out, *out_axes)
    return (out * sx).astype(x.dtype) * w_scale.astype(x.dtype)


def _trunk_fwd(cfg, out_axes, x, w_q, w_scale):
    return trunk_matmul(cfg, out_axes, x, w_q, w_scale), (w_q, w_scale)


def _trunk_bwd(cfg, out_axes, res, g):
    w_q, w_scale = res
    w_deq = w_q.astype(g.dtype) * w_scale.astype(g.dtype)   # [K, N]
    dx = jnp.einsum("...n,kn->...k", g, w_deq)
    if out_axes is not None:
        # bwd of a column-parallel trunk is row-parallel: same RS rewrite
        from repro.distributed.sharding import shard
        dx = shard(dx, *out_axes)
    zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return dx, zero(w_q), zero(w_scale)


trunk_matmul.defvjp(_trunk_fwd, _trunk_bwd)


def conv_nhwc(x, w, stride: int = 1, padding: str = "SAME"):
    """The repo's one NHWC/HWIO conv wrapper (models and oracles reuse it)."""
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def trunk_conv_residuals(x, w_q, w_scale):
    """Residuals for the conv-trunk STE backward (shared by the
    int8_native path here and the Pallas dispatch in kernels/ops.py).

    zeros_like(x) carries only shape/dtype into the backward (the conv is
    linear in x, so its vjp never reads the primal values); XLA DCEs it.
    """
    return (w_q, w_scale, jnp.zeros_like(x))


def trunk_conv_ste_bwd(stride: int, padding: str, res, g):
    """Shared STE backward: dx = conv_transpose(g, dequant(w)), no dW."""
    w_q, w_scale, x0 = res
    w_deq = w_q.astype(g.dtype) * w_scale.reshape(1, 1, 1, -1).astype(g.dtype)
    dx = jax.vjp(lambda t: conv_nhwc(t, w_deq, stride, padding), x0)[1](g)[0]
    zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return dx, zero(w_q), zero(w_scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def trunk_conv(cfg: cim_lib.CiMConfig, stride: int, padding: str,
               x, w_q, w_scale):
    """Conv analogue of :func:`trunk_matmul`: frozen int8 ROM trunk conv.

    Forward im2cols the NHWC input, quantises each patch row dynamically
    and runs the (possibly non-ideal) CiM macro model on the patch matrix;
    backward is the straight-through estimator
    ``dx = conv_transpose(g, dequant(w))``.  No dW is ever produced.

    x: [N, H, W, C_in] float;  w_q: [KH, KW, C_in, C_out] int8;
    w_scale: per-output-channel f32 (any shape reducible to [C_out]).
    """
    kh, kw, c_in, c_out = w_q.shape
    patches, _ = cim_lib.im2col(x, kh, kw, stride, padding)
    p_q, sp = quant.quantize_activations(patches)
    out = cim_lib.cim_matmul_model(p_q, w_q.reshape(kh * kw * c_in, c_out),
                                   cfg)
    return (out * sp).astype(x.dtype) * w_scale.reshape(-1).astype(x.dtype)


def _trunk_conv_fwd(cfg, stride, padding, x, w_q, w_scale):
    out = trunk_conv(cfg, stride, padding, x, w_q, w_scale)
    return out, trunk_conv_residuals(x, w_q, w_scale)


def _trunk_conv_bwd(cfg, stride, padding, res, g):
    return trunk_conv_ste_bwd(stride, padding, res, g)


trunk_conv.defvjp(_trunk_conv_fwd, _trunk_conv_bwd)


def trunk_matmul_dequant(cfg, x, w_q, w_scale):
    """Paper-faithful *baseline* trunk path: dequantise to bf16/f32 and use a
    dense matmul with fake-quantised activations (STE built in).  2x the
    weight HBM traffic of the int8-native path; kept as the reference the
    §Perf optimization is measured against."""
    del cfg
    x_hq = quant.fake_quant_ste(x)
    w = w_q.astype(x.dtype) * w_scale.astype(x.dtype)
    return x_hq @ w


def trunk_conv_dequant(cfg, stride: int, padding: str, x, w_q, w_scale):
    """Conv analogue of :func:`trunk_matmul_dequant`: dequantised weights +
    fake-quantised activations on a plain XLA conv (STE built in)."""
    del cfg
    w = w_q.astype(x.dtype) * w_scale.astype(x.dtype)
    return conv_nhwc(quant.fake_quant_ste(x), w, stride, padding)


# ---------------------------------------------------------------------------
# ReBranch linear layer
# ---------------------------------------------------------------------------

def init_linear(key, d_in: int, d_out: int, spec: ReBranchSpec,
                *, w_init: jax.Array | None = None,
                use_bias: bool = False, name_scale: float = 1.0):
    """Create ReBranch linear params.

    If ``w_init`` is given the trunk ROM image is built from it (freeze a
    pretrained matrix); otherwise the trunk is randomly initialised and
    frozen (pretraining-from-scratch is done *before* freezing, see
    examples/transfer_rebranch.py).
    """
    kw, kc, ku = jax.random.split(key, 3)
    dt = spec.param_dtype
    if w_init is None:
        w_init = jax.random.normal(kw, (d_in, d_out), dt)
        w_init = w_init * (name_scale / np.sqrt(d_in))
    if not spec.enabled:
        p = {"sram": {"w": w_init.astype(dt)}}
        if use_bias:
            p["sram"]["b"] = jnp.zeros((d_out,), dt)
        return p

    w_q, w_scale = quant.quantize_weights(w_init, axis=0)
    rom = {"w_q": w_q, "w_scale": w_scale.astype(dt)}
    p = {"rom": rom, "sram": {}}
    if spec.branch_enabled:
        d_c = max(1, d_in // spec.d_ratio)
        d_u = max(1, d_out // spec.u_ratio)
        # Fixed (ROM) projections: scaled Gaussian — an oblivious JL-style
        # sketch; frozen at "tape-out".
        rom["C"] = (jax.random.normal(kc, (d_in, d_c), dt) / np.sqrt(d_in))
        rom["U"] = (jax.random.normal(ku, (d_u, d_out), dt) / np.sqrt(d_u))
        p["sram"]["core"] = jnp.zeros((d_c, d_u), dt)   # branch starts at 0
    if use_bias:
        p["sram"]["b"] = jnp.zeros((d_out,), dt)
    return p


def apply_linear(params, x, spec: ReBranchSpec, t1_axes=None,
                 out_axes=None):
    """Apply a ReBranch linear layer (or a plain linear if disabled).

    t1_axes: optional logical-axis annotation for the branch compress
    output.  Row-parallel trunks (o/down projections) pass
    ('batch','seq','mlp') so GSPMD reduce-scatters t1 instead of
    all-reducing + re-gathering the d_in/D-wide intermediate.
    out_axes: optional constraint applied DIRECTLY to the trunk matmul
    output (before the branch add) — placing it adjacent to the dot lets
    the SPMD partitioner turn the row-parallel partial-sum all-reduce
    into a reduce-scatter.
    """
    if not spec.enabled:
        y = x @ params["sram"]["w"].astype(x.dtype)
        b = params["sram"].get("b")
        return y if b is None else y + b.astype(x.dtype)

    rom, sram = params["rom"], params["sram"]
    if spec.trunk_skip:
        # Draft path (speculative decode): the ROM trunk never runs —
        # only the SRAM-resident branch contributes, at ~1/compression
        # of the layer's FLOPs.  No engine resolution either: the draft
        # is pure XLA on the branch tensors.  Branchless ROM sites
        # contribute zero (their whole signal lives in the trunk).
        if spec.branch_enabled and "core" in sram:
            with jax.named_scope("branch"):
                y = _branch(params, x)
        else:
            y = jnp.zeros((*x.shape[:-1], rom["w_q"].shape[-1]), x.dtype)
        b = sram.get("b")
        return y if b is None else y + b.astype(x.dtype)
    from repro import engine as engine_lib   # deferred: avoids import cycle
    eng = engine_lib.resolve(spec)           # strict + capability-gated
    if (spec.branch_enabled and "core" in sram
            and "matmul" in eng.capabilities.fused_ops):
        # fused trunk+branch pass: one read of x computes the CiM dot and
        # the compress sketch (t1_axes/out_axes hints don't apply — the
        # fused kernel owns its own layout)
        y = eng.fused_matmul(spec.cim, x, rom["w_q"], rom["w_scale"],
                             rom["C"], sram["core"], rom["U"])
        b = sram.get("b")
        return y if b is None else y + b.astype(x.dtype)
    with jax.named_scope("trunk"):
        y = eng.matmul(spec.cim, x, rom["w_q"], rom["w_scale"],
                       out_axes=out_axes)

    if spec.branch_enabled and "core" in sram:
        with jax.named_scope("branch"):
            y = y + _branch(params, x, t1_axes)
    b = sram.get("b")
    return y if b is None else y + b.astype(x.dtype)


def _branch(params, x, t1_axes=None):
    """The branch term ``(x @ C) @ (core @ U)`` in ``x``'s dtype.

    Reassociated epilogue: core@U is a tiny [d_in/D, d_out] precompute
    whose output sharding matches the trunk's, so the branch adds NO
    collectives and NO wide intermediate activation ((t1@core)@U would
    materialise a d_out/U-wide tensor and force an all-gather under TP).
    A site that :func:`serving_branch` prepared in ``x``'s dtype reads
    its stored ``C`` and ``core @ U``; any other site casts and
    multiplies its weights here, on every call.
    """
    served = params.get(SERVED_KEY)
    if served is not None and served["W"].dtype != x.dtype:
        served = None
    if served is None:
        c = params["rom"]["C"].astype(x.dtype)
        w = branch_weight(params["sram"]["core"], params["rom"]["U"],
                          x.dtype)
    else:
        c, w = served["C"], served["W"]
    t1 = x @ c
    if t1_axes is not None:
        from repro.distributed.sharding import shard
        t1 = shard(t1, *t1_axes)
    return t1 @ w


def branch_weight(core, u, dtype):
    """``core @ U`` in ``dtype``: [d_in/D, d_out], or [E, d_in/D, d_out]
    for an expert stack, whose per-expert cores share one ``U``.  The
    one expression every call and :func:`serving_branch` evaluate."""
    return jnp.matmul(core.astype(dtype), u.astype(dtype))


def _is_site(node) -> bool:
    """A ReBranch site whose branch a call reads: a linear (``C``,
    ``core`` and ``U`` stacked alike, read by :func:`apply_linear`) or
    an expert stack (``core`` with one more, expert, axis; read by
    ``models.moe.apply_expert_linear``)."""
    rom, sram = node.get(ROM_KEY), node.get("sram")
    if not (isinstance(rom, dict) and isinstance(sram, dict)
            and "C" in rom and "U" in rom and "core" in sram):
        return False
    return (rom["C"].ndim == rom["U"].ndim
            and sram["core"].ndim in (rom["C"].ndim, rom["C"].ndim + 1))


def _sites(node, out: list) -> None:
    """Every site under ``node``, in a fixed walk order."""
    if isinstance(node, dict):
        if _is_site(node):
            out.append(node)
            return
        for v in node.values():
            _sites(v, out)
    elif type(node) in (list, tuple):
        for v in node:
            _sites(v, out)


def _core_at_u(core, u, dtype):
    """:func:`branch_weight`, one layer at a time for stacked sites, as
    each layer's call computed it."""
    lead = u.shape[:-2]                   # layer axes (U has no expert axis)
    if not lead:
        return branch_weight(core, u, dtype)
    w = jax.lax.map(lambda cu: branch_weight(cu[0], cu[1], dtype),
                    (core.reshape(-1, *core.shape[len(lead):]),
                     u.reshape(-1, *u.shape[-2:])))
    return w.reshape(*lead, *w.shape[1:])


@functools.partial(jax.jit, static_argnums=(1,))
def _derive(branches, dtype):
    return [(None if c.dtype == dtype else c.astype(dtype),
             _core_at_u(core, u, dtype)) for c, core, u in branches]


def serving_branch(params, dtype):
    """The params tree as serving reads it: each ReBranch site gains
    ``params[SERVED_KEY] = {"C": C.astype(dtype), "W": core@U}``, the
    branch in the activation ``dtype``, so a device call that applies it
    (:func:`_branch`, ``models.moe.apply_expert_linear``) converts and
    multiplies no weight.  An MoE expert stack is a site too: its
    ``C`` and ``U`` are shared by the experts and its ``W`` holds one
    ``core @ U`` per expert ([..., E, d_in/D, d_out]).  One jitted
    call for the whole tree; the expressions are those ``apply_linear``
    evaluates per call.  The results are bit-identical on the CPU only:
    on a TPU the compiler may round the derived ``core @ U`` otherwise
    than inside each call, and fuse the served branch product into the
    trunk's epilogue, so served and raw logits differ by bfloat16
    rounding.  Every other leaf is the source's own array (no copy),
    and a ``C`` already in ``dtype`` is stored as itself.  A derived
    leaf goes stale when ``core`` changes: derive again from the new
    tree.  Trees that train, checkpoint or swap are the source ones:
    derived leaves are never trainable state.

    Every site is derived, whatever its engine: the tree does not say
    which spec governs a site.  A site whose engine fuses the branch
    into its trunk kernel never reads its derived leaves, which then
    cost their bytes (a bfloat16 ``C`` and ``W``) and nothing else.

    Returns ``(served_tree, sites, nbytes)``: the sites derived (each
    layer of a stacked site counts) and the bytes newly allocated.
    """
    dtype = jnp.dtype(dtype)
    sites: list = []
    _sites(params, sites)
    derived = _derive([(s[ROM_KEY]["C"], s["sram"]["core"], s[ROM_KEY]["U"])
                       for s in sites], dtype)
    served = {id(s): {"C": s[ROM_KEY]["C"] if c is None else c, "W": w}
              for s, (c, w) in zip(sites, derived)}

    def build(node):
        if isinstance(node, dict):
            if id(node) in served:
                return {**{k: v for k, v in node.items() if k != SERVED_KEY},
                        SERVED_KEY: served[id(node)]}
            return {k: build(v) for k, v in node.items()}
        if type(node) in (list, tuple):
            return type(node)(build(v) for v in node)
        return node

    n_sites = sum(math.prod(s[ROM_KEY]["C"].shape[:-2]) for s in sites)
    nbytes = sum(w.nbytes + (0 if c is None else c.nbytes)
                 for c, w in derived)
    return build(params), n_sites, nbytes


def freeze_to_rom(params_dense, key, spec: ReBranchSpec):
    """Convert a tree of plain linears ({'sram': {'w': ..}}) into ReBranch
    form — the 'tape-out' step: quantise trunks into ROM, attach branches."""
    def conv(path, node):
        if isinstance(node, dict) and "sram" in node and "w" in node.get("sram", {}):
            w = node["sram"]["w"]
            sub = jax.random.fold_in(key, hash(path) % (2 ** 31))
            p = init_linear(sub, w.shape[0], w.shape[1], spec, w_init=w,
                            use_bias="b" in node["sram"])
            if "b" in node["sram"]:
                p["sram"]["b"] = node["sram"]["b"]
            return p
        if isinstance(node, dict):
            return {k: conv(path + (k,), v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(conv(path + (i,), v) for i, v in enumerate(node))
        return node
    return conv((), params_dense)
