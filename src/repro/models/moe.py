"""Mixture-of-Experts block (granite-moe, qwen2-moe, deepseek-v2) with
ReBranch experts.

Two computations of one layer:

* :func:`apply_held_experts` — dropless, for serving: every prefill and
  decode call (a cache is given) takes it.  The router scores all
  ``num_experts`` at its published width and keeps the top k; the layer
  computes only the experts this chip holds (``cfg.experts_held``, a
  contiguous range: one chip's share under expert parallelism), each
  densely over the call's tokens under its gate, and adds the shared
  experts once.  No token is dropped, so a row's output does not depend
  on which rows share its batch.  At the few tokens of a decode step or
  a prefill chunk (32 or fewer) this reads each held expert's weights
  once, as a grouped matmul would, and its extra MACs cost no time: the
  int8 ridge of a v5e is about 240 tokens.
* :func:`apply_moe_block` — the training ``forward``: the TPU-standard
  grouped capacity scheme (MaxText-style): tokens are split into groups;
  within each group every token's top-k experts get a capacity slot
  (priority = token order); one-hot dispatch/combine einsums move tokens
  to/from the stacked expert computation.  Only this path may drop
  tokens (over capacity); it runs an uncut layer (all experts held).

ReBranch on experts: stacked trunk weights [E, d_in, d_out] are frozen
int8 ROM; the branch shares the fixed compress/decompress sketches across
experts (they are oblivious projections) and keeps a per-expert trainable
core [E, d_in/D, d_out/U] — so >90% of MoE parameters are ROM, matching
the paper's budget.  The router is tiny and stays trainable ("SRAM").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant, rebranch
from repro.distributed.sharding import shard
from repro.models.config import ArchConfig


# ---------------------------------------------------------------------------
# stacked ReBranch expert linear
# ---------------------------------------------------------------------------

def init_expert_linear(key, n_exp: int, d_in: int, d_out: int, spec):
    kw, kc, ku = jax.random.split(key, 3)
    w = jax.random.normal(kw, (n_exp, d_in, d_out), jnp.float32) / np.sqrt(d_in)
    if not spec.enabled:            # SRAM residency: plain trainable stack
        return {"sram": {"w": w}}
    absmax = jnp.max(jnp.abs(w), axis=1, keepdims=True)        # [E,1,out]
    w_scale = jnp.maximum(absmax, 1e-8) / 127.0
    w_q = jnp.clip(jnp.round(w / w_scale), -127, 127).astype(jnp.int8)
    d_c = max(1, d_in // spec.d_ratio)
    d_u = max(1, d_out // spec.u_ratio)
    return {
        "rom": {
            "w_q": w_q, "w_scale": w_scale.astype(spec.param_dtype),
            "C": jax.random.normal(kc, (d_in, d_c), spec.param_dtype)
                 / np.sqrt(d_in),
            "U": jax.random.normal(ku, (d_u, d_out), spec.param_dtype)
                 / np.sqrt(d_u),
        },
        "sram": {"core": jnp.zeros((n_exp, d_c, d_u), spec.param_dtype)},
    }


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _stacked_trunk_matmul(x, w_q, w_scale):
    """y[e] = quant(x[e]) @ w_q[e] * scales — int8 MXU path, STE backward."""
    x_q, sx = quant.quantize_activations(x)                    # [E,C,d]
    out = jax.lax.dot_general(
        x_q, w_q, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32).astype(jnp.float32)
    return (out * sx * w_scale.astype(jnp.float32)).astype(x.dtype)


def _stm_fwd(x, w_q, w_scale):
    return _stacked_trunk_matmul(x, w_q, w_scale), (w_q, w_scale)


def _stm_bwd(res, g):
    w_q, w_scale = res
    w_deq = w_q.astype(g.dtype) * w_scale.astype(g.dtype)      # [E,d,f]
    dx = jnp.einsum("ecf,edf->ecd", g, w_deq)
    zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return dx, zero(w_q), zero(w_scale)


_stacked_trunk_matmul.defvjp(_stm_fwd, _stm_bwd)


def apply_expert_linear(params, x):
    """x: [E, C, d_in] -> [E, C, d_out] (reassociated branch epilogue —
    see core.rebranch.apply_linear).  SRAM-resident stacks (no ROM image;
    'blocks.moe' mapped to SRAM) are a plain batched matmul.  A stack
    that ``rebranch.serving_branch`` prepared in ``x``'s dtype reads its
    stored ``C`` and per-expert ``core @ U``; any other casts and
    multiplies its weights here, on every call."""
    if "rom" not in params:
        return jnp.einsum("ecd,edf->ecf", x,
                          params["sram"]["w"].astype(x.dtype))
    rom, sram = params["rom"], params["sram"]
    y = _stacked_trunk_matmul(x, rom["w_q"], rom["w_scale"])
    served = params.get(rebranch.SERVED_KEY)
    if served is not None and served["W"].dtype == x.dtype:
        c, cu = served["C"], served["W"]
    else:
        c = rom["C"].astype(x.dtype)
        cu = rebranch.branch_weight(sram["core"], rom["U"], x.dtype)
    t1 = x @ c                                                  # [E,C,dc]
    return y + jnp.einsum("ecd,edf->ecf", t1, cu)


# ---------------------------------------------------------------------------
# MoE block
# ---------------------------------------------------------------------------

def init_moe_block(key, cfg: ArchConfig):
    """Router over all ``num_experts``; expert stacks of the held ones."""
    ks = jax.random.split(key, 6)
    spec = cfg.rebranch
    d, ff, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.num_experts
    lo, hi = cfg.held_range
    p = {
        "router": {"sram": {
            "w": jax.random.normal(ks[0], (d, e), jnp.float32) / np.sqrt(d)}},
        "experts": {
            "gate": init_expert_linear(ks[1], hi - lo, d, ff, spec),
            "up": init_expert_linear(ks[2], hi - lo, d, ff, spec),
            "down": init_expert_linear(ks[3], hi - lo, ff, d, spec),
        },
    }
    if cfg.num_shared_experts:
        from repro.models import layers
        shared_ff = cfg.num_shared_experts * (cfg.moe_d_ff or cfg.d_ff)
        p["shared"] = layers.init_mlp(ks[4], cfg, d_ff=shared_ff)
    if cfg.num_shared_experts and cfg.shared_expert_gate:
        p["shared_gate"] = {"sram": {
            "w": jax.random.normal(ks[5], (d, 1), jnp.float32) / np.sqrt(d)}}
    return p


def _capacity(cfg: ArchConfig) -> int:
    g, k, e = cfg.moe_group_size, cfg.num_experts_per_tok, cfg.num_experts
    c = int(np.ceil(g * k * cfg.moe_capacity_factor / e))
    return max(4, -(-c // 4) * 4)          # multiple of 4


def _top_k(probs, cfg: ArchConfig):
    gates, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.moe_norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def _shared(params, x, y, cfg: ArchConfig):
    """``y`` plus the shared experts' output (gated where the config
    gates it), under the named scope ``shared``."""
    if "shared" not in params:
        return y
    from repro.models import layers
    with jax.named_scope("shared"):
        sh = layers.apply_mlp(params["shared"], x, cfg)
        if "shared_gate" in params:
            sg = jax.nn.sigmoid(
                jnp.einsum("bsd,do->bso", x.astype(jnp.float32),
                           params["shared_gate"]["sram"]["w"]))
            sh = sh * sg.astype(x.dtype)
    return y + sh


def apply_held_experts(params, x, cfg: ArchConfig, live=None):
    """Dropless MoE over the held experts (see the module docstring).

    x [B, S, d] -> (y [B, S, d], assigned [E_held] int32): the token
    assignments each held expert computed, counting the tokens of rows
    where ``live`` [B] is true (every row when None)."""
    b, s, d = x.shape
    lo, hi = cfg.held_range
    xf = x.reshape(b * s, d)
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            logits = jnp.matmul(xf.astype(jnp.float32),
                                params["router"]["sram"]["w"],
                                precision=jax.lax.Precision.HIGHEST)
            gates, idx = _top_k(jax.nn.softmax(logits, axis=-1), cfg)
            hit = idx[..., None] == jnp.arange(lo, hi)       # [T, k, Eh]
            g = jnp.sum(jnp.where(hit, gates[..., None], 0.0), 1)
            rows = jnp.ones((b,), bool) if live is None else live
            counted = hit & jnp.repeat(rows, s)[:, None, None]
            assigned = jnp.sum(counted, (0, 1), dtype=jnp.int32)
        with jax.named_scope("experts"):
            xe = jnp.broadcast_to(xf, (hi - lo, *xf.shape))
            ex = params["experts"]
            h = jax.nn.silu(apply_expert_linear(ex["gate"], xe)) \
                * apply_expert_linear(ex["up"], xe)
            h = apply_expert_linear(ex["down"], h)            # [Eh, T, d]
            y = jnp.einsum("te,etd->td", g, h.astype(jnp.float32))
        y = _shared(params, x, y.astype(x.dtype).reshape(b, s, d), cfg)
    return y, assigned


def apply_moe_block(params, x, cfg: ArchConfig):
    if cfg.held_range != (0, cfg.num_experts):
        raise ValueError(
            f"{cfg.name!r} holds experts {cfg.held_range} of "
            f"{cfg.num_experts}: the capacity dispatch runs an uncut "
            f"layer (serving calls take apply_held_experts)")
    b, s, d = x.shape
    t = b * s
    g = min(cfg.moe_group_size, t)
    n_groups = -(-t // g)
    pad = n_groups * g - t
    xf = x.reshape(t, d)
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    xg = xf.reshape(n_groups, g, d)
    xg = shard(xg, "batch", None, "embed")

    e, k = cfg.num_experts, cfg.num_experts_per_tok
    cap = _capacity(cfg)

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        params["router"]["sram"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = _top_k(probs, cfg)                           # [G,g,k]

    dispatch = jnp.zeros((n_groups, g, e, cap), jnp.bfloat16)
    combine = jnp.zeros((n_groups, g, e, cap), jnp.float32)
    counts = jnp.zeros((n_groups, e), jnp.int32)
    for j in range(k):
        oh = jax.nn.one_hot(idx[..., j], e, dtype=jnp.int32)  # [G,g,E]
        pos = jnp.cumsum(oh, axis=1) - oh + counts[:, None, :]
        pos_j = jnp.sum(pos * oh, axis=-1)                    # [G,g]
        keep = (pos_j < cap).astype(jnp.float32)
        slot = jax.nn.one_hot(pos_j, cap, dtype=jnp.float32)  # [G,g,C]
        d_j = (oh.astype(jnp.float32)[..., None] * slot[:, :, None, :]
               * keep[..., None, None])
        dispatch = dispatch + d_j.astype(jnp.bfloat16)
        combine = combine + d_j * gates[..., j, None, None]
        counts = counts + jnp.sum(oh, axis=1)

    dispatch = shard(dispatch, "batch", None, "expert", None)
    combine = shard(combine, "batch", None, "expert", None)

    # [G,g,E,C] x [G,g,d] -> [E, G*C, d].  CRITICAL: the dispatched-slot
    # dim (G*C) must stay sharded over the data axis — leaving it
    # replicated makes every device compute the whole fleet's expert
    # branch (HLO showed 1.6e15 replicated flops + 3.8 TB all-gathers).
    x_exp = jnp.einsum("gtec,gtd->egcd", dispatch,
                       xg.astype(jnp.bfloat16))
    x_exp = x_exp.reshape(e, n_groups * cap, d).astype(x.dtype)
    x_exp = shard(x_exp, "expert", "batch", "embed")

    hg = apply_expert_linear(params["experts"]["gate"], x_exp)
    hu = apply_expert_linear(params["experts"]["up"], x_exp)
    h = jax.nn.silu(hg) * hu
    h = shard(h, "expert", "batch", "expert_mlp")
    h = apply_expert_linear(params["experts"]["down"], h)
    h = shard(h, "expert", "batch", "embed")

    h = h.reshape(e, n_groups, cap, d)
    y = jnp.einsum("gtec,egcd->gtd", combine,
                   h.astype(jnp.float32)).astype(x.dtype)
    y = y.reshape(n_groups * g, d)[:t].reshape(b, s, d)
    return shard(_shared(params, x, y, cfg), "batch", "seq", None)


def aux_load_balance_loss(params, x, cfg: ArchConfig):
    """Switch-style auxiliary loss (exported for the training loop)."""
    b, s, d = x.shape
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"]["sram"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    frac = jnp.mean(jax.nn.one_hot(idx, cfg.num_experts), axis=(0, 1, 2))
    imp = jnp.mean(probs, axis=(0, 1))
    return cfg.num_experts * jnp.sum(frac * imp)
