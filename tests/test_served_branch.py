"""The served branch (``rebranch.serving_branch``): each ReBranch site's
``C`` and ``core @ U`` derived once per params tree in the activation
dtype, and read by ``apply_linear`` in place of per-call casts and
products.

  * on a tree with derived leaves ``apply_linear`` equals the raw tree
    bit for bit;
  * a batcher serves the greedy tokens of solo prefill/decode on the raw
    tree, and after a swap the tokens of a fresh server on that
    scenario, holding the leaves a fresh derivation gives;
  * the decode step on the served tree multiplies no two parameters and
    casts no branch leaf, where on the raw tree it does both.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro import configs, serve
from repro.core import rebranch
from repro.core.rebranch import ReBranchSpec
from repro.scenario import ScenarioStore, split_params
from repro.serve import LMServer
from repro.serve.pool import PagedPool, SlotPool
from repro.serve.scheduler import ContinuousBatcher

MAX_LEN = 48
GENS = (5, 3, 6)


def _model(arch: str, dtype: str):
    """The smoke config of ``arch`` with activations in ``dtype``."""
    model_id = f"{arch.replace('_', '-')}-smoke-{dtype}"
    serve.register(serve.ModelEntry(
        model_id=model_id,
        config=lambda: dataclasses.replace(configs.get_smoke(arch),
                                           dtype=dtype)), override=True)
    return serve.compile_entry(model_id)


def _with_cores(params, seed: int):
    """``params`` with every ReBranch ``core`` drawn from ``seed``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    return jax.tree_util.tree_unflatten(treedef, [
        0.3 * jax.random.normal(k, leaf.shape, leaf.dtype)
        if getattr(path[-1], "key", None) == "core" else leaf
        for k, (path, leaf) in zip(keys, flat)])


def _prompts(vocab):
    rng = np.random.default_rng(7)
    return [rng.integers(0, vocab, size=n) for n in (19, 6, 11)]


def _solo(model, params, prompt, n_new):
    """Greedy tokens of one prompt through solo prefill + decode_step."""
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode_step)
    cache = model.init_cache(1, MAX_LEN, dtype=jnp.float32)
    logits, cache = prefill(
        params, {"tokens": jnp.asarray(np.asarray(prompt)[None])}, cache)
    out = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(n_new - 1):
        logits, cache = decode(params, jnp.asarray([[out[-1]]], jnp.int32),
                               cache)
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


def _served_leaves(tree) -> dict:
    return {jax.tree_util.keystr(p): leaf
            for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
            if f"['{rebranch.SERVED_KEY}']" in jax.tree_util.keystr(p)}


# -- apply_linear -----------------------------------------------------------

@pytest.mark.parametrize("trunk_skip", [False, True])
@pytest.mark.parametrize("use_bias", [False, True])
@pytest.mark.parametrize("d_in, d_out", [(64, 256), (256, 64)],
                         ids=["gate", "down"])
def test_apply_linear_served_equals_raw(d_in, d_out, use_bias, trunk_skip):
    spec = ReBranchSpec(trunk_skip=trunk_skip)
    params = rebranch.init_linear(jax.random.PRNGKey(0), d_in, d_out, spec,
                                  use_bias=use_bias)
    params = _with_cores(params, 1)
    if use_bias:
        params["sram"]["b"] = jnp.linspace(-1, 1, d_out)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 5, d_in), jnp.bfloat16)
    served, sites, nbytes = rebranch.serving_branch(params, jnp.bfloat16)
    derived = served[rebranch.SERVED_KEY]
    assert derived["C"].dtype == derived["W"].dtype == jnp.bfloat16
    assert derived["W"].shape == (d_in // 4, d_out)
    assert sites == 1 and nbytes == derived["C"].nbytes + derived["W"].nbytes
    apply = jax.jit(lambda p, x: rebranch.apply_linear(p, x, spec))
    want = np.asarray(apply(params, x).astype(jnp.float32))
    np.testing.assert_array_equal(
        np.asarray(apply(served, x).astype(jnp.float32)), want)
    # derived leaves in another dtype are not read: today's path runs
    other, _, _ = rebranch.serving_branch(params, jnp.float16)
    np.testing.assert_array_equal(
        np.asarray(apply(other, x).astype(jnp.float32)), want)


# -- the batcher ------------------------------------------------------------

@pytest.mark.parametrize("arch, dtype, paged, chunk", [
    ("qwen2_vl_2b", "float32", True, 8),
    ("qwen2_vl_2b", "bfloat16", True, 8),
    ("falcon_mamba_7b", "float32", False, 0),   # ssm: no paging, no chunks
])
def test_batcher_serves_solo_tokens_of_the_raw_tree(arch, dtype, paged,
                                                    chunk):
    model, _ = _model(arch, dtype)
    raw = _with_cores(model.init(jax.random.PRNGKey(0)), 3)
    pool = PagedPool(model, 3, 18, 8, MAX_LEN) if paged \
        else SlotPool(model, 3, MAX_LEN)
    b = ContinuousBatcher(model, raw, pool, prefill_chunk=chunk)
    prompts = _prompts(model.cfg.vocab_size)
    reqs = [b.submit(p, g) for p, g in zip(prompts, GENS)]
    b.drain(max_steps=200)
    for r, p, g in zip(reqs, prompts, GENS):
        assert r.tokens == _solo(model, raw, p, g)
    # every source leaf is served as itself; only the derived ones are new
    src = dict((jax.tree_util.keystr(p), leaf) for p, leaf in
               jax.tree_util.tree_flatten_with_path(raw)[0])
    served = dict((jax.tree_util.keystr(p), leaf) for p, leaf in
                  jax.tree_util.tree_flatten_with_path(b.params)[0])
    derived = _served_leaves(b.params)
    assert derived and set(served) - set(derived) == set(src)
    assert all(served[k] is v for k, v in src.items())
    assert {leaf.dtype for leaf in derived.values()} == {jnp.dtype(dtype)}
    assert b.branch_prepares == 1


def test_fused_engines_ignore_the_served_leaves():
    """An engine that fuses the branch into its trunk kernel is handed
    the derived leaves too, and reads none of them: a served site with
    its ``W`` zeroed applies as the raw site."""
    spec = ReBranchSpec(trunk_impl="pallas_fused")
    params = _with_cores(rebranch.init_linear(
        jax.random.PRNGKey(0), 128, 256, spec), 1)
    served, _, _ = rebranch.serving_branch(params, jnp.bfloat16)
    served[rebranch.SERVED_KEY]["W"] = jnp.zeros_like(
        served[rebranch.SERVED_KEY]["W"])
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 128), jnp.bfloat16)
    apply = jax.jit(lambda p, x: rebranch.apply_linear(p, x, spec))
    np.testing.assert_array_equal(
        np.asarray(apply(served, x).astype(jnp.float32)),
        np.asarray(apply(params, x).astype(jnp.float32)))


def test_swap_serves_a_fresh_servers_tokens():
    model, plan = _model("qwen2_vl_2b", "bfloat16")
    p_a = _with_cores(model.init(jax.random.PRNGKey(0)), 4)
    branch_a, trunk = split_params(p_a)
    branch_b = split_params(_with_cores(p_a, 5))[0]
    store = ScenarioStore(model, plan)
    store.register("a", branch=branch_a)
    store.register("b", branch=branch_b)
    prompt = _prompts(model.cfg.vocab_size)[0]

    def server(params, scenario):
        return LMServer(model, params, n_slots=2, max_len=MAX_LEN,
                        store=store, scenario=scenario, prefill_chunk=8)

    srv = server(jax.tree.map(jnp.array, p_a), "a")
    first = srv.submit(prompt, 6)
    srv.drain(max_steps=100)
    swapped = srv.submit(prompt, 6, scenario="b")
    srv.drain(max_steps=100)
    assert srv.scenario == "b" and srv.batcher.branch_prepares == 2
    p_b = rebranch.combine(branch_b, trunk)
    fresh = server(p_b, "b")
    again = fresh.submit(prompt, 6)
    fresh.drain(max_steps=100)
    assert swapped.tokens == again.tokens != first.tokens
    want = _served_leaves(rebranch.serving_branch(p_b, jnp.bfloat16)[0])
    got = _served_leaves(srv.params)
    assert set(got) == set(want)
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(got[k], np.float32),
                                      np.asarray(v, np.float32))


# -- the decode program -----------------------------------------------------

def _weight_only_work(fn, params, *args):
    """(dot_generals whose two operands both come from ``params`` alone,
    float32 -> bfloat16 casts of a branch leaf) in ``fn``'s jaxpr."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    branch = {i for i, (p, _) in enumerate(flat)
              if getattr(p[-1], "key", None) in ("C", "U", "core")}
    closed = jax.make_jaxpr(fn)(params, *args)
    n_args = len(jax.tree.leaves(args))
    deps = [frozenset({i}) for i in range(len(flat))] + [None] * n_args
    found = {"dots": 0, "casts": 0}
    _walk(closed.jaxpr, deps, branch, found)
    return found["dots"], found["casts"]


def _walk(jaxpr, deps, branch, found):
    """Each value's set of parameter leaves, or None once it depends on
    anything else (an activation, a cache, a loop carry)."""
    env = dict(zip(jaxpr.invars, deps))
    env.update((v, frozenset()) for v in jaxpr.constvars)

    def dep(a):
        return frozenset() if isinstance(a, jcore.Literal) else env.get(a)

    def union(ds):
        return None if any(d is None for d in ds) else frozenset().union(*ds)

    for eqn in jaxpr.eqns:
        ins = [dep(a) for a in eqn.invars]
        prim = eqn.primitive.name
        if prim == "dot_general" and all(d is not None for d in ins):
            found["dots"] += 1
        if (prim == "convert_element_type" and ins[0]
                and ins[0] & branch
                and eqn.invars[0].aval.dtype == jnp.float32
                and eqn.params["new_dtype"] == jnp.bfloat16):
            found["casts"] += 1
        outs = [union(ins)] * len(eqn.outvars)
        if prim == "scan":
            nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
            inner = ins[:nc] + [None] * ncar + ins[nc + ncar:]
            got = _walk(eqn.params["jaxpr"].jaxpr, inner, branch, found)
            outs = [None] * ncar + got[ncar:]
        elif prim == "while":
            cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
            carry = [None] * (len(ins) - cn - bn)
            _walk(eqn.params["body_jaxpr"].jaxpr, ins[cn:cn + bn] + carry,
                  branch, found)
            outs = [None] * len(eqn.outvars)
        elif prim == "cond":
            for br in eqn.params["branches"]:
                _walk(br.jaxpr, ins[1:], branch, found)
            outs = [None] * len(eqn.outvars)
        else:
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if isinstance(inner, jcore.Jaxpr) and \
                        len(inner.invars) == len(ins):
                    outs = _walk(inner, ins, branch, found)
        env.update(zip(eqn.outvars, outs))
    return [dep(v) for v in jaxpr.outvars]


@pytest.mark.parametrize("arch", ["qwen2_vl_2b", "hymba_1_5b"])
def test_served_decode_step_does_no_weight_only_work(arch):
    model, _ = _model(arch, "bfloat16")
    raw = model.init(jax.random.PRNGKey(0))
    served, _, _ = rebranch.serving_branch(raw, jnp.bfloat16)
    cache = model.init_cache(2, MAX_LEN, dtype=jnp.float32)
    tok = jnp.zeros((2, 1), jnp.int32)
    dots, casts = _weight_only_work(model.decode_step, raw, tok, cache)
    assert dots > 0 and casts > 0          # core @ U and its casts, per call
    assert _weight_only_work(model.decode_step, served, tok, cache) == (0, 0)
