"""The check catches a broken timed path: each fault the cells can have.

A system is set up once per cell at small sizes on the CPU (the chip
check off), then driven through short windows with the program broken
underneath; ``check`` must come out not correct for every fault, and
correct with nothing broken.  (One chip: no exchange between chips to
leave out.)
"""

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests import smoke

SEED = 2**31 + 99


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return smoke.write(tmp_path_factory.mktemp("bench"))


_SYSTEMS = {}


def system(manifest, cell):
    if cell not in _SYSTEMS:
        s, _ = run.prepare(manifest, cell, SEED, 1.0, require_tpu=False,
                           cache=False)
        s.setup()
        _SYSTEMS[cell] = s
    return _SYSTEMS[cell]


def _correct(s, seconds=1.0):
    w = s.window(seconds)
    return all(c["pass"] for c in s.check(w).values())


# -- CNN: the answer comes from CNNServer's jitted forward ----------------

def _cnn_answer_altered(f):
    return lambda p, x: f(p, x).at[0].multiply(-1.0)


def _cnn_half_batch(f):
    def g(p, x):
        half = x.shape[0] // 2
        out = f(p, x)
        return out.at[half:].set(0.0)
    return g


CNN_FAULTS = {"darknet19.b8": [_cnn_answer_altered, _cnn_half_batch],
              "darknet19.stream1": [_cnn_answer_altered]}


@pytest.mark.parametrize("cell,fault", [(c, f) for c, fs in CNN_FAULTS.items()
                                        for f in fs],
                         ids=lambda v: getattr(v, "__name__", v))
def test_cnn_fault_is_not_correct(manifest, cell, fault):
    s = system(manifest, cell)
    good = s.server._forward
    s.server._forward = fault(good)
    try:
        assert not _correct(s)
    finally:
        s.server._forward = good
    assert _correct(s)


# -- LM: tokens come from the batcher's jitted decode_step ----------------

def _lm_state_unchanged(b):
    plain = jax.jit(b.model.decode_step)
    return lambda p, t, c: (plain(p, t, c)[0], c)


def _lm_half_batch(b):
    f = jax.jit(b.model.decode_step)

    def g(p, t, c):
        lg, c2 = f(p, t, c)
        return lg.at[lg.shape[0] // 2:].set(0.0), c2
    return g


def _lm_token_altered(b):
    """One row's token is replaced at every step, each step another."""
    f = jax.jit(b.model.decode_step)

    def g(p, t, c):
        lg, c2 = f(p, t, c)
        row = b.step_count % lg.shape[0]
        alt = (jnp.argmax(lg[row, -1]) + 1) % lg.shape[-1]
        return lg.at[row, -1, alt].set(jnp.max(lg) + 1.0), c2
    return g


LM_FAULTS = [_lm_state_unchanged, _lm_half_batch, _lm_token_altered]


@pytest.mark.parametrize("cell", ["qwen2vl2b.decode", "qwen2vl2b.chat"])
@pytest.mark.parametrize("fault", LM_FAULTS, ids=lambda f: f.__name__)
def test_lm_fault_is_not_correct(manifest, cell, fault):
    s = system(manifest, cell)
    b = s.server.batcher
    good = b._decode
    b._decode = fault(b)
    try:
        assert not _correct(s, 2.0)
    finally:
        b._decode = good
