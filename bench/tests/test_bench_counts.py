"""Operation and byte counts against the program's own shapes, and the
peaks table."""

import jax
import jax.numpy as jnp
import pytest

from bench import harness, peaks
from bench.counts import conv, lm
from bench.tests import smoke


def _body(name):
    return harness.Manifest().config(name)["body"]


def test_cnn_sites_and_ops_match_the_program():
    from repro.models import cnn
    body = _body("darknet19_416")
    cfg = harness.Manifest().system("cnn").cnn_config(body)
    shapes = cnn.conv_site_shapes(cfg)
    assert [s[:5] for s in shapes] == conv.sites(body)
    macs = sum(hw * hw * k * k * ci * co for _, k, ci, co, hw, _ in shapes)
    for batch in (1, 8):
        assert conv.forward_work(body, batch)["int8_ops"] == 2 * batch * macs
    assert abs(macs / 1e9 - 12.64) < 0.01          # GMAC per 416 px image


def _lm_smoke():
    body = {**_body("qwen2_vl_2b"), **smoke.LM}
    cfg = harness.Manifest().system("lm").arch_config(body)
    from repro import deploy
    return body, deploy.compile_model(cfg, engine=body["engine"])


def test_lm_param_bytes_match_the_program():
    body, model = _lm_smoke()
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    got = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert lm.param_bytes(body) == got


def test_lm_decode_ops_agree_with_hlo_cost():
    """hlo_cost counts the program's dots: per row every projection,
    attention over the whole cache horizon and the readout, plus the
    branch's ``core @ U`` product the program forms once per call."""
    from repro.launch import hlo_cost
    body, model = _lm_smoke()
    rows, horizon = 3, 32
    params = jax.eval_shape(model.init, jax.random.key(0))
    cache = jax.eval_shape(lambda: model.init_cache(rows, horizon,
                                                    dtype=jnp.float32))
    tok = jax.ShapeDtypeStruct((rows, 1), jnp.int32)
    text = jax.jit(model.decode_step).lower(params, tok, cache).compile() \
        .as_text()
    flops = hlo_cost.analyse_text(text)["flops"]
    w = lm.token_work(body, horizon)
    d, u = body["rebranch"]["d_ratio"], body["rebranch"]["u_ratio"]
    proj = [(i, i // d, o // u, o) for i, o in lm.projections(body)]
    # the program reassociates each branch as (x @ C) @ (core @ U)
    algorithm = sum(2 * (i * c + c * k + k * o) for i, c, k, o in proj)
    program = sum(2 * (i * c + c * o) for i, c, k, o in proj)
    core_u = sum(2 * c * k * o for i, c, k, o in proj)
    mine = rows * (w["int8_ops"] + w["float_ops"]) + body[
        "num_hidden_layers"] * (core_u + rows * (program - algorithm))
    assert mine == pytest.approx(flops, rel=0.01)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v99")


def test_least_time_names_the_binding_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = peaks.least_time(p, int8_ops=393e12, bytes_moved=1.0)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = peaks.least_time(p, float_ops=1.0, bytes_moved=819e9)
    assert (t, bound) == (pytest.approx(1.0), "memory")
