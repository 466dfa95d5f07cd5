"""The paper's own models: VGG-8, ResNet-18, DarkNet-19, Tiny-YOLO.

ReBranchConv (paper Fig. 7-8): frozen int8 trunk conv (ROM) in parallel
with  1x1 compress -> KxK trainable core conv -> 1x1 decompress  (branch;
the point-wise (de)compression layers are fixed, only the core trains).
With D=U=4 the branch holds 1/16 of the trunk parameters.

NHWC layout.  The trunk conv resolves ``spec.trunk_impl`` through the
``repro.engine`` registry (the same TrunkEngine the ReBranch linears use
— 'int8_native' / 'dequant' / 'pallas' out of the box, strict resolution,
every backward the straight-through estimator so branch training is
identical under all engines).  Per-layer engine / ROM-vs-SRAM overrides
come in through ``cfg.rebranch_overrides`` (see ``config.spec_for`` and
``repro.deploy.compile_model``); each conv is addressed by a site name
('convs.3', 'stem', 'stages.1.0.conv2', 'head.0', ...).

With ``cfg.fuse_bn_act`` the inference BN affine + activation fold into
the trunk conv's engine epilogue (one fused pass instead of three
feature-map sweeps) — numerically the same inference-style BN.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine as engine_lib
from repro.core import rebranch as rebranch_lib
from repro.core.rebranch import ReBranchSpec
from repro.distributed.sharding import shard
from repro.engine import base as engine_base
from repro.models.config import spec_for


# ---------------------------------------------------------------------------
# ReBranch convolution
# ---------------------------------------------------------------------------

_conv = rebranch_lib.conv_nhwc


def _pool(x):
    """2x2 max pool + re-constrain onto the CNN serving layout (batch over
    pod, spatial H over data — the halo-exchange conv's native sharding).
    The constraint keeps GSPMD from drifting to a replicated layout after
    the windowed reduction; no-op without a mesh."""
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                              (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    return shard(x, "cnn_batch", "cnn_h")


def init_conv(key, k: int, c_in: int, c_out: int, spec: ReBranchSpec,
              *, w_init=None):
    ks = jax.random.split(key, 3)
    if w_init is None:
        w_init = (jax.random.normal(ks[0], (k, k, c_in, c_out), jnp.float32)
                  * np.sqrt(2.0 / (k * k * c_in)))
    if not spec.enabled:
        return {"sram": {"w": w_init}}
    absmax = jnp.max(jnp.abs(w_init), axis=(0, 1, 2), keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    w_q = jnp.clip(jnp.round(w_init / scale), -127, 127).astype(jnp.int8)
    p = {"rom": {"w_q": w_q, "w_scale": scale}, "sram": {}}
    if spec.branch_enabled:
        c_c = max(1, c_in // spec.d_ratio)
        c_u = max(1, c_out // spec.u_ratio)
        p["rom"]["C"] = (jax.random.normal(ks[1], (1, 1, c_in, c_c))
                         / np.sqrt(c_in)).astype(jnp.float32)
        p["rom"]["U"] = (jax.random.normal(ks[2], (1, 1, c_u, c_out))
                         / np.sqrt(c_u)).astype(jnp.float32)
        p["sram"]["core"] = jnp.zeros((k, k, c_c, c_u), jnp.float32)
    return p


def apply_conv(params, x, spec: ReBranchSpec, stride: int = 1,
               epilogue: engine_base.ConvEpilogue | None = None):
    """One ReBranch conv through the resolved TrunkEngine.

    epilogue: optional per-channel affine + activation folded into the
    trunk pass (the scale rides the engine's existing dequant epilogue;
    with a live branch the activation is deferred until after the branch
    add so act(BN(trunk + branch)) semantics are preserved).
    """
    if not spec.enabled:
        return engine_base.finish(_conv(x, params["sram"]["w"], stride),
                                  epilogue)
    rom = params["rom"]
    eng = engine_lib.resolve(spec)          # strict + capability-gated
    has_branch = spec.branch_enabled and "core" in params["sram"]
    # engines without epilogue support get None (handing them one would be
    # silently dropped); the layer applies the whole epilogue itself then
    fuse = epilogue is not None and eng.capabilities.epilogue
    if has_branch and "conv" in eng.capabilities.fused_ops:
        # one pass over the shared patch matrix computes trunk AND branch;
        # the epilogue applies after the in-kernel branch add, exactly the
        # act(BN(trunk + branch)) the unfused path reconstructs below
        y = eng.fused_conv(spec.cim, x, rom["w_q"], rom["w_scale"],
                           rom["C"], params["sram"]["core"], rom["U"],
                           stride=stride, padding="SAME",
                           epilogue=epilogue if fuse else None)
        return y if fuse else engine_base.finish(y, epilogue)
    trunk_ep = (epilogue.without_act() if has_branch else epilogue) \
        if fuse else None
    with jax.named_scope("trunk"):
        y = eng.conv(spec.cim, x, rom["w_q"], rom["w_scale"],
                     stride=stride, padding="SAME", epilogue=trunk_ep)
    if has_branch:
        with jax.named_scope("branch"):
            t = _conv(x, rom["C"].astype(x.dtype), 1)
            t = _conv(t, params["sram"]["core"].astype(x.dtype), stride)
            b = _conv(t, rom["U"].astype(x.dtype), 1)
        if fuse:
            if epilogue.scale is not None:
                b = b * epilogue.scale.astype(b.dtype)
            return engine_base.activate(y + b, epilogue)
        return engine_base.finish(y + b, epilogue)
    return y if fuse or epilogue is None else engine_base.finish(y, epilogue)


def conv_trainable_frac(spec: ReBranchSpec) -> float:
    return 1.0 / (spec.d_ratio * spec.u_ratio)


def freeze_to_rom(params, key, spec: ReBranchSpec):
    """'Tape-out' a pretrained all-trainable CNN: every plain conv
    ({'sram': {'w': [k,k,cin,cout]}}) becomes a ReBranch conv (int8 ROM
    trunk + fixed C/U + zero-init trainable core).  Dense heads (2D 'w')
    and BN stay trainable ("SRAM")."""
    idx = [0]

    def conv_node(node):
        w = node["sram"]["w"]
        if w.ndim != 4:
            return node                      # dense head: stays SRAM
        idx[0] += 1
        sub = jax.random.fold_in(key, idx[0])
        return init_conv(sub, w.shape[0], w.shape[2], w.shape[3], spec,
                         w_init=w)

    def walk(node):
        if isinstance(node, dict):
            if set(node.keys()) == {"sram"} and "w" in node["sram"]:
                return conv_node(node)
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node

    return walk(params)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def _bn_init(c):
    return {"sram": {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,)),
                     "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}}


def bn_epilogue(bn_params, act: str | None = None) -> engine_base.ConvEpilogue:
    """Inference BN (frozen statistics; YOLoC deploys inference chips), plus
    an optional activation, as a fusable conv epilogue: a per-output-channel
    affine that rides the trunk's dequant multiply in one fused elementwise
    pass.  The ONE home of the BN affine — _bn_apply is defined from it."""
    s = bn_params["sram"]
    inv = jax.lax.rsqrt(s["var"] + 1e-5) * s["scale"]
    return engine_base.ConvEpilogue(scale=inv, bias=s["bias"] - s["mean"] * inv,
                                    act=act)


def _bn_apply(p, x, train: bool = False):
    return engine_base.finish(x, bn_epilogue(p))


def _leaky(x):
    return jax.nn.leaky_relu(x, 0.1)


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    num_classes: int = 100
    input_size: int = 32
    rebranch: ReBranchSpec = dataclasses.field(default_factory=ReBranchSpec)
    head_anchors: int = 5            # YOLO heads
    head_classes: int = 20           # VOC
    # per-layer mapping overrides ((site, ReBranchSpec), ...) — see
    # config.spec_for / repro.deploy.compile_model
    rebranch_overrides: tuple = ()
    # fold BN + activation into the trunk conv's engine epilogue
    fuse_bn_act: bool = False


# ---------------------------------------------------------------------------
# VGG-8  (paper's CIFAR classifier)
# ---------------------------------------------------------------------------

VGG8_CHANNELS = (64, 64, 128, 128, 256, 256)   # conv layers, pool every 2


def init_vgg8(key, cfg: CNNConfig):
    keys = jax.random.split(key, len(VGG8_CHANNELS) + 1)
    convs, bns = [], []
    c_in = 3
    for i, c in enumerate(VGG8_CHANNELS):
        convs.append(init_conv(keys[i], 3, c_in, c,
                               spec_for(cfg, f"convs.{i}")))
        bns.append(_bn_init(c))
        c_in = c
    fc = {"sram": {
        "w": jax.random.normal(keys[-1],
                               (c_in * (cfg.input_size // 8) ** 2,
                                cfg.num_classes)) * 0.01,
        "b": jnp.zeros((cfg.num_classes,))}}
    return {"convs": convs, "bns": bns, "fc": fc}


def apply_vgg8(params, x, cfg: CNNConfig):
    for i, (conv, bn) in enumerate(zip(params["convs"], params["bns"])):
        spec = spec_for(cfg, f"convs.{i}")
        if cfg.fuse_bn_act:
            x = apply_conv(conv, x, spec, epilogue=bn_epilogue(bn, "relu"))
        else:
            x = jax.nn.relu(_bn_apply(bn, apply_conv(conv, x, spec)))
        if i % 2 == 1:
            x = _pool(x)
    x = x.reshape(x.shape[0], -1)
    return x @ params["fc"]["sram"]["w"] + params["fc"]["sram"]["b"]


# ---------------------------------------------------------------------------
# ResNet-18 (CIFAR variant)
# ---------------------------------------------------------------------------

RESNET18_STAGES = ((64, 2, 1), (128, 2, 2), (256, 2, 2), (512, 2, 2))


def init_resnet18(key, cfg: CNNConfig):
    key, k0 = jax.random.split(key)
    params = {"stem": init_conv(k0, 3, 3, 64, spec_for(cfg, "stem")),
              "stem_bn": _bn_init(64), "stages": []}
    c_in = 64
    for si, (c_out, blocks, stride) in enumerate(RESNET18_STAGES):
        stage = []
        for b in range(blocks):
            key, k1, k2, k3 = jax.random.split(key, 4)
            st = stride if b == 0 else 1
            site = f"stages.{si}.{b}"
            blk = {
                "conv1": init_conv(k1, 3, c_in, c_out,
                                   spec_for(cfg, f"{site}.conv1")),
                "bn1": _bn_init(c_out),
                "conv2": init_conv(k2, 3, c_out, c_out,
                                   spec_for(cfg, f"{site}.conv2")),
                "bn2": _bn_init(c_out),
            }
            if st != 1 or c_in != c_out:
                blk["proj"] = init_conv(k3, 1, c_in, c_out,
                                        spec_for(cfg, f"{site}.proj"))
                blk["proj_bn"] = _bn_init(c_out)
            stage.append(blk)
            c_in = c_out
        params["stages"].append(stage)
    key, kf = jax.random.split(key)
    params["fc"] = {"sram": {
        "w": jax.random.normal(kf, (512, cfg.num_classes)) * 0.01,
        "b": jnp.zeros((cfg.num_classes,))}}
    return params


def apply_resnet18(params, x, cfg: CNNConfig):
    def conv_bn(conv_p, bn_p, xx, spec, st=1, act=None):
        # fuse_bn_act: the BN affine always folds into the conv epilogue;
        # the activation only where it legally follows the conv (bn2 /
        # proj_bn feed the residual add, so their act stays outside)
        if cfg.fuse_bn_act:
            return apply_conv(conv_p, xx, spec, st,
                              epilogue=bn_epilogue(bn_p, act))
        y = _bn_apply(bn_p, apply_conv(conv_p, xx, spec, st))
        return jax.nn.relu(y) if act == "relu" else y

    x = conv_bn(params["stem"], params["stem_bn"], x,
                spec_for(cfg, "stem"), act="relu")
    for si, (stage, (_, _, stride)) in enumerate(
            zip(params["stages"], RESNET18_STAGES)):
        for b, blk in enumerate(stage):
            st = stride if b == 0 else 1
            site = f"stages.{si}.{b}"
            h = conv_bn(blk["conv1"], blk["bn1"], x,
                        spec_for(cfg, f"{site}.conv1"), st, act="relu")
            h = conv_bn(blk["conv2"], blk["bn2"], h,
                        spec_for(cfg, f"{site}.conv2"))
            sc = x
            if "proj" in blk:
                sc = conv_bn(blk["proj"], blk["proj_bn"], x,
                             spec_for(cfg, f"{site}.proj"), st)
            x = shard(jax.nn.relu(h + sc), "cnn_batch", "cnn_h")
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["fc"]["sram"]["w"] + params["fc"]["sram"]["b"]


# ---------------------------------------------------------------------------
# DarkNet-19 backbone + YOLO head (the paper's headline model), Tiny-YOLO
# ---------------------------------------------------------------------------

# (channels, kernel) per layer; 'M' = maxpool  — DarkNet-19 (YOLOv2 backbone)
DARKNET19 = [
    (32, 3), "M", (64, 3), "M",
    (128, 3), (64, 1), (128, 3), "M",
    (256, 3), (128, 1), (256, 3), "M",
    (512, 3), (256, 1), (512, 3), (256, 1), (512, 3), "M",
    (1024, 3), (512, 1), (1024, 3), (512, 1), (1024, 3),
]

TINY_YOLO = [
    (16, 3), "M", (32, 3), "M", (64, 3), "M", (128, 3), "M",
    (256, 3), "M", (512, 3), "M", (1024, 3),
]


def _init_darknet(key, plan, cfg: CNNConfig, head_convs):
    convs, bns = [], []
    c_in = 3
    ci = 0
    for item in plan:
        if item == "M":
            continue                      # pools carry no params
        c, k = item
        key, k1 = jax.random.split(key)
        convs.append(init_conv(k1, k, c_in, c, spec_for(cfg, f"convs.{ci}")))
        bns.append(_bn_init(c))
        c_in = c
        ci += 1
    # detection head: conv stack + 1x1 predictor (trainable — "SRAM")
    head = []
    for hi, (c, k) in enumerate(head_convs):
        key, k1 = jax.random.split(key)
        head.append({"conv": init_conv(k1, k, c_in, c,
                                       spec_for(cfg, f"head.{hi}")),
                     "bn": _bn_init(c)})
        c_in = c
    key, k1 = jax.random.split(key)
    n_out = cfg.head_anchors * (5 + cfg.head_classes)
    # the 1x1 predictor is always a plain trainable conv (no site: there
    # is nothing to override — it never freezes into ROM)
    pred = init_conv(k1, 1, c_in, n_out,
                     dataclasses.replace(cfg.rebranch, enabled=False))
    return {"convs": convs, "bns": bns, "head": head, "pred": pred}


def init_darknet19(key, cfg: CNNConfig):
    return _init_darknet(key, DARKNET19, cfg,
                         head_convs=[(1024, 3), (1024, 3)])


def init_tiny_yolo(key, cfg: CNNConfig):
    return _init_darknet(key, TINY_YOLO, cfg, head_convs=[(512, 3)])


def apply_darknet(params, x, cfg: CNNConfig):
    plan = DARKNET19 if cfg.name == "darknet19" else TINY_YOLO

    def conv_bn_leaky(conv_p, bn_p, xx, spec):
        if cfg.fuse_bn_act:
            return apply_conv(conv_p, xx, spec,
                              epilogue=bn_epilogue(bn_p, "leaky_relu"))
        return _leaky(_bn_apply(bn_p, apply_conv(conv_p, xx, spec)))

    i = 0
    for item in plan:
        if item == "M":
            x = _pool(x)
        else:
            x = conv_bn_leaky(params["convs"][i], params["bns"][i], x,
                              spec_for(cfg, f"convs.{i}"))
            i += 1
    for hi, blk in enumerate(params["head"]):
        x = conv_bn_leaky(blk["conv"], blk["bn"], x,
                          spec_for(cfg, f"head.{hi}"))
    x = apply_conv(params["pred"], x,
                   dataclasses.replace(cfg.rebranch, enabled=False))
    b, h, w, _ = x.shape
    return x.reshape(b, h, w, cfg.head_anchors, 5 + cfg.head_classes)


MODEL_REGISTRY = {
    "vgg8": (init_vgg8, apply_vgg8),
    "resnet18": (init_resnet18, apply_resnet18),
    "darknet19": (init_darknet19, apply_darknet),
    "tiny_yolo": (init_tiny_yolo, apply_darknet),
}


def conv_site_shapes(cfg: CNNConfig) -> list | None:
    """Every conv site this config's init/apply consult through spec_for,
    with its geometry: ``(site, k, c_in, c_out, out_hw, stride)`` tuples
    in forward order (out_hw is the conv's own output resolution, the MAC
    basis: macs = out_hw^2 * k^2 * c_in * c_out per inference).

    Kept NEXT TO the model builders so a structural edit (new conv, new
    projection rule) updates the enumeration in the same file.  None for
    names outside MODEL_REGISTRY.  (The 1x1 'pred' conv has no site: it
    never freezes into ROM.)  ``repro.plan.sites`` wraps these into the
    validated site tree the placement subsystem consumes."""
    if cfg.name == "vgg8":
        out, c_in, hw = [], 3, cfg.input_size
        for i, c in enumerate(VGG8_CHANNELS):
            out.append((f"convs.{i}", 3, c_in, c, hw, 1))
            c_in = c
            if i % 2 == 1:
                hw //= 2
        return out
    if cfg.name == "resnet18":
        hw = cfg.input_size
        out, c_in = [("stem", 3, 3, 64, hw, 1)], 64
        for si, (c_out, blocks, stride) in enumerate(RESNET18_STAGES):
            for b in range(blocks):
                st = stride if b == 0 else 1
                hw_out = -(-hw // st)               # SAME stride st
                site = f"stages.{si}.{b}"
                out.append((f"{site}.conv1", 3, c_in, c_out, hw_out, st))
                out.append((f"{site}.conv2", 3, c_out, c_out, hw_out, 1))
                if st != 1 or c_in != c_out:        # same rule as init
                    out.append((f"{site}.proj", 1, c_in, c_out, hw_out, st))
                c_in, hw = c_out, hw_out
        return out
    if cfg.name in ("darknet19", "tiny_yolo"):
        plan = DARKNET19 if cfg.name == "darknet19" else TINY_YOLO
        head = ([(1024, 3), (1024, 3)] if cfg.name == "darknet19"
                else [(512, 3)])
        out, c_in, hw, ci = [], 3, cfg.input_size, 0
        for item in plan:
            if item == "M":
                hw //= 2
                continue
            c, k = item
            out.append((f"convs.{ci}", k, c_in, c, hw, 1))
            c_in = c
            ci += 1
        for hi, (c, k) in enumerate(head):
            out.append((f"head.{hi}", k, c_in, c, hw, 1))
            c_in = c
        return out
    return None


def override_sites(cfg: CNNConfig) -> set | None:
    """The site-name set of :func:`conv_site_shapes` (None when unknown)."""
    shapes = conv_site_shapes(cfg)
    return None if shapes is None else {s[0] for s in shapes}


def count_macs_and_params(init_fn, apply_fn, cfg: CNNConfig):
    """Static MAC/param counts for the energy model (jaxpr-free estimate)."""
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: init_fn(k, cfg), key)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params)
                   if hasattr(l, "shape"))
    x = jax.ShapeDtypeStruct((1, cfg.input_size, cfg.input_size, 3),
                             jnp.float32)

    macs = {"n": 0}

    def count(p, xx):
        return apply_fn(p, xx, cfg)

    # count conv MACs from the jaxpr
    jaxpr = jax.make_jaxpr(count)(params, x)

    def walk(jpr):
        for eqn in jpr.eqns:
            if eqn.primitive.name == "conv_general_dilated":
                out = eqn.outvars[0].aval.shape
                wshape = eqn.invars[1].aval.shape
                macs["n"] += int(np.prod(out)) * int(
                    np.prod(wshape[:3]))      # H*W*... * (kh*kw*cin)
            elif eqn.primitive.name in ("dot_general",):
                a = eqn.invars[0].aval.shape
                o = eqn.outvars[0].aval.shape
                macs["n"] += int(np.prod(o)) * int(a[-1])
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)
    walk(jaxpr.jaxpr)
    return n_params, macs["n"]
