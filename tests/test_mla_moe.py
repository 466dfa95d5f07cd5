"""DeepSeek-V2-style serving at the smoke size: latent attention over
the paged pool and the dropless held-expert MoE layer, against the plain
reference (``bench/configs/deepseek_v2_ref.py``, which imports nothing of
the program), on seeded weights with non-zero cores.

  (a) the logits of ``LMServer``'s prefill, chunked prefill and paged
      decode calls equal the reference's full forward pass;
  (b) the shares of the 8 "chips" of an expert-parallel layer, with the
      shared experts counted once, sum to the uncut layer;
  (c) a row's logits are the same alone and in a batch whose every
      token routes to one expert (the capacity dispatch drops there);
  (d) the served expert branch equals the per-call branch bit for bit;
  (e) speculative decode is refused on latent attention.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.configs import deepseek_v2_ref as ref
from repro import configs, serve
from repro.core import rebranch
from repro.models import api, moe
from repro.serve import LMServer
from repro.serve import trace

MAX_LEN = 48
CFG = configs.get_smoke("deepseek_v2_lite")


def _body(cfg=CFG) -> dict:
    """The reference's configuration keys for ``cfg``."""
    y = cfg.rope_scaling
    return {"num_attention_heads": cfg.num_heads,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "rope_scaling": dataclasses.asdict(y),
            "published": {"n_routed_experts": cfg.num_experts},
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.moe_norm_topk, "routed_scaling_factor": 1}


def _with_cores(params, seed: int):
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    return jax.tree_util.tree_unflatten(treedef, [
        0.3 * jax.random.normal(k, leaf.shape, leaf.dtype)
        if getattr(path[-1], "key", None) == "core" else leaf
        for k, (path, leaf) in zip(keys, flat)])


@pytest.fixture(scope="module")
def cell():
    model_id = "deepseek-v2-lite-smoke-test"
    serve.register(serve.ModelEntry(model_id=model_id, config=lambda: CFG),
                   override=True)
    model, _ = serve.compile_entry(model_id)
    return model, _with_cores(model.init(jax.random.PRNGKey(0)), 1)


def _spy(batcher, calls):
    """Record the logits of every prefill and decode call the batcher
    makes, each with the (request, position) it predicts from."""
    run_prefill, decode = batcher._run_prefill, batcher._decode

    def on_prefill(req, solo, pos, end):
        logits, solo = run_prefill(req, solo, pos, end)
        calls.append(("prefill", [(0, req.rid, end - 1)], logits))
        return logits, solo

    def on_decode(params, tok, cache):
        rows = [(slot, r.rid, r.prompt.size + len(r.tokens) - 1)
                for slot, r in batcher._active.items()]
        logits, cache = decode(params, tok, cache)
        calls.append(("decode", rows, logits))
        return logits, cache

    batcher._run_prefill, batcher._decode = on_prefill, on_decode


def _server(model, params, **kw):
    return LMServer(model, params, n_slots=3, max_len=MAX_LEN, paged=True,
                    block_size=8, prefill_chunk=8, **kw)


def test_a_server_logits_match_the_reference(cell):
    """Prefill (whole prompts, and 8-token chunks of longer ones) and
    paged decode through ``LMServer``, against the reference's full
    forward over each request's prompt and served tokens.

    Tolerances, over the largest |logit|: 1e-4 at nine positions in ten
    and 1e-2 at every one.  Program and reference both compute in
    float32 here but sum in other orders, which moves a logit by float32
    rounding (about 1e-6 of its size).  That can flip a discrete choice:
    an activation's int8 rounding at a .5 boundary, or which of two
    near-tied experts the router picks; one such flip moved one
    request's later logits by up to 5e-3 of the largest (seeded here)."""
    model, params = cell
    srv = _server(model, params)
    calls = []
    _spy(srv.batcher, calls)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, CFG.vocab_size, n) for n in (19, 6, 11, 3)]
    reqs = [srv.submit(p, g) for p, g in zip(prompts, (7, 9, 5, 6))]
    srv.drain(max_steps=200)
    want = {}
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1])])
        want[r.rid] = ref.logits(params, seq, np.arange(seq.size), _body(),
                                 8, MAX_LEN)
    scale = max(np.abs(w).max() for w in want.values())
    err = {"prefill": [], "decode": []}
    for kind, rows, logits in calls:
        logits = np.asarray(logits)
        for slot, rid, pos in rows:
            err[kind].append(np.abs(logits[slot, -1]
                                    - want[rid][pos]).max() / scale)
    # 19 and 11 prefill in chunks (3 + 2 calls), 6 and 3 whole
    assert len(err["prefill"]) == 7
    assert len(err["decode"]) == 7 + 9 + 5 + 6 - 4
    every = np.concatenate([err["prefill"], err["decode"]])
    assert every.max() < 1e-2
    assert np.mean(every < 1e-4) >= 0.9


def test_b_expert_shares_sum_to_the_uncut_layer():
    """Eight chips hold one expert each (EP 8): their routed parts, and
    the shared experts counted once, add up to the reference's uncut
    MoE layer."""
    cfg = dataclasses.replace(CFG, dtype="float32")
    key = jax.random.PRNGKey(4)
    full = _with_cores(moe.init_moe_block(key, cfg), 5)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 5, cfg.d_model))
    routed = {k: v for k, v in full.items() if k != "shared"}
    total = 0.0
    for lo in range(cfg.num_experts):
        share = dataclasses.replace(cfg, experts_held=(lo, lo + 1))
        part = {**routed, "experts": jax.tree.map(
            lambda a: a[lo:lo + 1] if a.ndim == 3 and a.shape[0]
            == cfg.num_experts else a, routed["experts"])}
        y, _ = moe.apply_held_experts(part, x, share)
        total = total + y
    from repro.models import layers
    total = total + layers.apply_mlp(full["shared"], x, cfg)
    want = jax.vmap(lambda xx: ref._moe(xx, full, (cfg.num_experts,
                                                   cfg.num_experts_per_tok,
                                                   False, 1.0), 8))(x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4 * float(jnp.abs(want).max()))


def _skewed(params):
    """Every token's router logits put expert 0 first by a wide margin."""
    def skew(block):
        w = block["moe"]["router"]["sram"]["w"]
        w = w.at[..., 0].add(50.0 * jnp.sign(w[..., 0]) + 0.0)
        return {**block, "moe": {**block["moe"], "router": {"sram": {
            "w": w}}}}
    return {**params, "layers": skew(params["layers"])}


def test_c_rows_are_batch_invariant(cell):
    """One row's logits alone and beside seven rows that all route to
    expert 0, through prefill and a decode step of the served path;
    the training forward's capacity dispatch drops tokens there."""
    model, params = cell
    params = _skewed(params)
    rng = np.random.default_rng(8)
    rows = jnp.asarray(rng.integers(0, CFG.vocab_size, (8, 12)))

    def serve_rows(tokens):
        cache = model.init_cache(tokens.shape[0], MAX_LEN,
                                 dtype=jnp.float32)
        first, cache = jax.jit(model.prefill)(params, {"tokens": tokens},
                                              cache)
        nxt = jnp.argmax(first[:, -1], -1)[:, None].astype(jnp.int32)
        second, _ = jax.jit(model.decode_step)(params, nxt, cache)
        return np.asarray(first[0, -1]), np.asarray(second[0, -1])

    alone, batched = serve_rows(rows[:1]), serve_rows(rows)
    for a, b in zip(alone, batched):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * np.abs(a).max())
    # the capacity dispatch at the same layer: a row's output moves with
    # its batch once the others fill expert 0's slots
    cfg = dataclasses.replace(CFG, moe_group_size=96,
                              moe_capacity_factor=1.0)
    layer = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(9), (8, 12, CFG.d_model))
    y_alone = moe.apply_moe_block(layer, x[:1], cfg)
    y_batch = moe.apply_moe_block(layer, x, cfg)
    assert float(jnp.abs(y_alone - y_batch[:1]).max()) > 1e-3
    h_alone, _ = moe.apply_held_experts(layer, x[:1], cfg)
    h_batch, _ = moe.apply_held_experts(layer, x, cfg)
    # float32 dots of 12 or 96 rows round alike to about 1e-7
    np.testing.assert_allclose(np.asarray(h_alone), np.asarray(h_batch[:1]),
                               rtol=0, atol=1e-5 * float(jnp.abs(
                                   h_alone).max()))


def test_d_served_expert_branch_is_the_per_call_branch():
    """``serving_branch`` derives each held expert's ``core @ U`` in the
    activation dtype; applying the served stack equals the raw stack bit
    for bit (CPU), whole MoE layers too."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16", experts_held=(2, 6))
    params = _with_cores(api.init(jax.random.PRNGKey(0), cfg), 2)
    served, sites, _ = rebranch.serving_branch(params, jnp.bfloat16)
    stack = served["layers"]["moe"]["experts"]["gate"]
    w = stack[rebranch.SERVED_KEY]["W"]
    n_moe = cfg.num_layers - cfg.first_k_dense
    assert w.shape == (n_moe, 4, cfg.d_model // 4, cfg.moe_d_ff)
    assert w.dtype == jnp.bfloat16
    layer = lambda t: jax.tree.map(lambda a: a[0], t["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 2, cfg.d_model),
                          jnp.bfloat16)
    apply = jax.jit(lambda p: moe.apply_held_experts(p, x, cfg)[0])
    np.testing.assert_array_equal(
        np.asarray(apply(layer(served)).astype(jnp.float32)),
        np.asarray(apply(layer(params)).astype(jnp.float32)))
    xe = jnp.broadcast_to(x.reshape(-1, cfg.d_model), (4, 6, cfg.d_model))
    one = jax.jit(moe.apply_expert_linear)
    np.testing.assert_array_equal(
        np.asarray(one(layer(served)["experts"]["up"], xe), np.float32),
        np.asarray(one(layer(params)["experts"]["up"], xe), np.float32))


def test_e_speculation_is_refused(cell):
    model, params = cell
    with pytest.raises(ValueError, match="latent attention"):
        _server(model, params, spec_k=2)


def test_held_expert_counter_counts_live_rows(cell):
    """The pool's ``held_tokens`` counter gains, each decode step, the
    assignments of live rows alone; with the recorder on each
    ``batcher.decode`` span carries its step's share."""
    model, params = cell
    srv = _server(model, params)
    srv.submit(np.arange(5) % CFG.vocab_size, 9)
    srv.step()                                   # prefill + first decode
    trace.enable()
    try:
        srv.drain(max_steps=50)
    finally:
        trace.disable()
    held = np.asarray(srv.pool.cache["counters"]["held_tokens"])
    steps = srv.batcher.step_count
    n_moe = CFG.num_layers - CFG.first_k_dense
    assert held.shape == (n_moe, CFG.num_experts)
    # one live row of the three: k assignments a layer a step
    assert held.sum() == steps * n_moe * CFG.num_experts_per_tok
    spans = [s for s in trace.snapshot()["spans"]
             if s["name"] == "batcher.decode"]
    got = [s["attrs"]["held_tokens"] for s in spans
           if "held_tokens" in s["attrs"]]
    assert got and all(g == n_moe * CFG.num_experts_per_tok for g in got)


def test_scopes_nest_and_change_only_metadata(cell, monkeypatch):
    """The served decode step carries ``mla`` > ``latent_expand`` and
    ``moe`` > ``router``, ``experts``, ``shared``; without the scopes it
    compiles to the same program, metadata aside."""
    from test_serve_trace import _no_scopes, _strip
    model, params = cell

    def build():
        srv = _server(model, params)
        b = srv.batcher
        return b._decode.lower(b.params, jnp.asarray(b._tok),
                               b.pool.cache).compile().as_text()

    scoped = build()
    paths = set(re.findall(r'op_name="([^"]*)"', scoped))
    for nest in ("mla/latent_expand", "mla/kv_write", "moe/router",
                 "moe/experts", "moe/shared"):
        assert any(nest in p for p in paths), nest
    monkeypatch.setattr(jax, "named_scope", _no_scopes)
    jax.clear_caches()
    plain = build()
    assert not any("moe/" in p or "mla/" in p
                   for p in re.findall(r'op_name="([^"]*)"', plain))
    assert _strip(scoped) == _strip(plain)
