"""The control comes out not correct: the plain reference with its
trunk in int4 (the nearest precision below the int8 the configurations
state), put in the program's place and read by the cell's own check.
Small sizes on the CPU; the readings at the cells' own sizes on the
chip are in PERF.md."""

import numpy as np
import pytest

from bench import run
from bench.tests import smoke


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    smoke_cnn = dict(smoke.CNN)
    smoke.CNN["input_size"] = 64          # a 2x2 grid: 32 px leaves 1x1
    try:
        return smoke.write(tmp_path_factory.mktemp("bench"))
    finally:
        smoke.CNN.update(smoke_cnn)


def _system(manifest, cell, seed):
    s, _ = run.prepare(manifest, cell, seed, 1.0, require_tpu=False,
                       cache=False)
    s.setup()
    return s


@pytest.mark.parametrize("seed", [1, 2])
def test_cnn_control_fails_the_limit(manifest, seed):
    s = _system(manifest, "darknet19.b8", seed)
    w = s.window(0.5)
    s.release()
    assert all(c["pass"] for c in s.check(w).values())
    ref = manifest.reference(s.body["reference"])
    limit = manifest.system("cnn").REL_L2_LIMIT
    for idx, _ in w.sample:
        want = np.asarray(ref.forward(s.params, s.frames[idx], s.body))
        low = np.asarray(ref.forward(s.params, s.frames[idx], s.body, 4))
        assert np.linalg.norm(low - want) / np.linalg.norm(want) > limit


@pytest.mark.parametrize("seed", [1, 2])
def test_lm_control_fails_the_limit(manifest, seed):
    s = _system(manifest, "qwen2vl2b.decode", seed)
    w = s.window(1.0)
    s.release()
    assert all(c["pass"] for c in s.check(w).values())
    limit = manifest.system("lm").GAP_LIMIT
    worst = max(float(s.gaps(r, 4).max()) for r in s.sample(w))
    assert worst > limit
