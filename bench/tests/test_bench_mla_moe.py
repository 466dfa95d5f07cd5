"""The ``dsv2lite.decode`` cell on the CPU at a small size: its counts
against the compiled program, its code path end to end, its readers.

The configuration is ``bench/configs/deepseek_v2_lite.json`` with every
width cut (two layers, the first dense) and the mix cut to match; the
system adapter, the reference and the readers are the benchmark's own.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, run
from bench.counts import mla_moe
from bench.systems import mla_moe_lm
from bench.tests import smoke

CELL = "dsv2lite.decode"
SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "moe_intermediate_size": 32,
         "n_routed_experts": 3, "num_experts_per_tok": 2,
         "vocab_size": 128, "dtype": "float32",
         "published": {"n_routed_experts": 8, "vocab_size": 102400}}
SERVING = {"rows": 4, "max_len": 64, "prefill_chunk": 8}
TRAFFIC = {"clients": 4, "size_pool": 64,
           "prompt_len": {"dist": "loguniform", "lo": 8, "hi": 24},
           "output_len": {"dist": "uniform", "lo": 4, "hi": 12}}


def _body() -> dict:
    body = harness.Manifest().config("deepseek_v2_lite")["body"]
    body.update(json.loads(json.dumps(SMALL)))
    body["rope_scaling"] = {**body["rope_scaling"],
                            "original_max_position_embeddings": 16}
    body["serving"] = {**body["serving"], **SERVING}
    return body


def _model(body):
    from repro import deploy
    return deploy.compile_model(mla_moe_lm.arch_config(body))


def _served_shapes(model, dtype):
    from repro.core import rebranch
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    return jax.eval_shape(
        lambda p: rebranch.serving_branch(p, dtype)[0], shapes)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    m = smoke.write(tmp)
    cfg = next(c for c in m.data["configs"] if c["name"] == "deepseek_v2_lite")
    with open(os.path.join(m.root, cfg["file"]), "w") as f:
        json.dump(_body(), f)
    traffic = harness.Manifest().traffic("decode32_zipf")
    os.makedirs(tmp / "traffic", exist_ok=True)
    with open(tmp / "traffic" / "decode32_zipf.json", "w") as f:
        json.dump({**traffic, **TRAFFIC}, f)
    return m


def test_decode_ops_agree_with_hlo_cost():
    """``program_step_ops`` against ``hlo_cost``'s count of the compiled
    served decode step (every row over the whole horizon, every held
    expert over every row; the router's dot is in float32)."""
    from repro.launch import hlo_cost
    body = _body()
    model = _model(body)
    rows, horizon = 3, 32
    cache = jax.eval_shape(lambda: model.init_cache(rows, horizon,
                                                    dtype=jnp.float32))
    tok = jax.ShapeDtypeStruct((rows, 1), jnp.int32)
    served = _served_shapes(model, jnp.dtype(model.cfg.dtype))
    text = jax.jit(model.decode_step).lower(served, tok,
                                            cache).compile().as_text()
    flops = hlo_cost.analyse_text(text)["flops"]
    w = mla_moe.program_step_ops(body, rows, horizon)
    assert w["int8_ops"] + w["float_ops"] == pytest.approx(flops, rel=0.01)


def test_param_bytes_match_the_served_tree():
    """What the decode step reads: every served leaf but the float32
    ``C``, ``U`` and ``core`` it does not read and the embedding."""
    body = _body()
    served = _served_shapes(_model(body), jnp.bfloat16)
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(served)[0]:
        names = [getattr(p, "key", None) for p in path]
        if names[0] == "embed" or (names[-1] in ("C", "U", "core")
                                   and "served" not in names):
            continue
        total += leaf.size * leaf.dtype.itemsize
    assert mla_moe.param_bytes(body) == total


def test_reference_takes_the_benchmark_config():
    """The program's prefill logits equal the reference's on the
    configuration file's keys, seeded weights with non-zero cores."""
    from bench import weights
    body = _body()
    model = _model(body)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make(shapes, 3, mla_moe_lm._rule(shapes))
    ref = harness.Manifest().reference(body["reference"])
    ids = np.random.default_rng(1).integers(0, body["vocab_size"], 20)
    want = ref.logits(params, ids, np.arange(20), body, 8, 32)
    cache = model.init_cache(1, 32, dtype=jnp.float32)
    got, _ = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(ids[None])}, cache)
    np.testing.assert_allclose(np.asarray(got[0, -1]), want[-1],
                               atol=1e-4 * np.abs(want).max())


def test_cell_runs_and_is_correct(manifest):
    args = run.parse(["--workload", CELL, "--seed", str(2**31 + 17),
                      "--seconds", "1.5", "--trace", "0"])
    res = run.execute(args, manifest, require_tpu=False, cache=False)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert res["checks"]["token_gap_max"]["limit"] == mla_moe_lm.GAP_LIMIT


def test_traced_run_reads_the_held_expert_counter(manifest):
    args = run.parse(["--workload", CELL, "--seed", "23", "--seconds", "1.5",
                      "--trace", "1"])
    res = run.execute(args, manifest, require_tpu=False, cache=False)
    assert res["correct"] is True
    # the CPU has no TPU plane: only the program's counter is read
    held = res["metrics"]["held_expert_tokens"]["value"]
    # 4 rows x 2 experts a token over 8 experts: about 1 per step
    assert 0.25 < held < 4.0


def test_prompt_ids_are_zipfian_over_the_slice(manifest):
    def prompts(seed):
        system, _ = run.prepare(manifest, CELL, seed, 1.0,
                                require_tpu=False, cache=False)
        return system, np.concatenate([system._prompt(512)
                                       for _ in range(8)])

    system, ids = prompts(5)
    assert ids.min() >= 0 and ids.max() < system.vocab
    _, counts = np.unique(ids, return_counts=True)
    # rank 1 of Zipf(1.1) over 128 ids holds about a quarter of the mass
    assert 0.15 < counts.max() / ids.size < 0.35
    np.testing.assert_array_equal(prompts(5)[1], ids)      # the seed's
    assert not np.array_equal(prompts(6)[1], ids)


@pytest.mark.parametrize("seed", [2**31 + 17, 41])
def test_int4_control_is_not_correct(manifest, monkeypatch, seed):
    """The control through the cell's own check: the reference with an
    int4 trunk in the program's place (its greedy token at each position
    read as the served one) comes out not correct in ``run.execute``'s
    result, where the program on the same seed is correct."""
    args = run.parse(["--workload", CELL, "--seed", str(seed),
                      "--seconds", "1.5", "--trace", "0"])
    good = run.execute(args, manifest, require_tpu=False, cache=False)
    assert good["correct"] is True, good["checks"]
    system = manifest.system("mla_moe_lm").System
    gaps = system.gaps
    monkeypatch.setattr(system, "gaps",
                        lambda self, req, bits=None: gaps(self, req, 4))
    res = run.execute(args, manifest, require_tpu=False, cache=False)
    assert res["correct"] is False
    assert res["checks"]["token_gap_max"]["value"] > mla_moe_lm.GAP_LIMIT
