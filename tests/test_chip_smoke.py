"""``chip_smoke.py`` off the chip: it refuses a host without a TPU, and
its phases run end to end at a tiny size on the CPU (the rehearsal
before a chip call; the chip run itself is full width).  The four-chip
phase runs in a subprocess on 4 forced host devices, so this process
keeps its one device."""

import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

from repro import configs
from repro.configs import paper_models

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_host_without_tpu(smoke, capsys):
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert "found platform 'cpu'" in err
    assert '"ok"' not in out


def test_cnn_phase_at_64px(smoke):
    cfg = dataclasses.replace(paper_models.DARKNET19_YOLO, input_size=64)
    r = smoke.run_cnn(cfg, seed=0, model_id="chip-smoke-darknet19-64",
                      batch=2)
    assert r["shape"] == (4, 2, 2, 5, 25)
    assert r["rel_err"] <= smoke.CNN_RTOL
    assert r["branch_effect"] >= 2 * smoke.CNN_RTOL


def test_lm_phase_on_the_smoke_config(smoke):
    r = smoke.run_lm(configs.get_smoke("qwen2_vl_2b"), seed=0,
                     model_id="chip-smoke-qwen2-vl", rows=4, max_len=256,
                     n_blocks=64, new_tokens=8)
    assert r["total"] == smoke.LM_REQUESTS * 8
    assert r["worst_gap"] <= smoke.LM_TIE_RTOL


def test_sharded_phase_on_four_host_devices():
    code = textwrap.dedent(f"""
        import dataclasses, importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {os.path.join(REPO, "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        from repro.configs import paper_models
        cfg = dataclasses.replace(paper_models.DARKNET19_YOLO,
                                  input_size=64)
        r = smoke.run_sharded(cfg, seed=0, n_chips=4)
        print("TRUNK_ERR", r["rel_err_trunk"])
        print("HALOS", r["halo_permutes"] > 0)
        print("FALLBACK", len(r["fallback"]), "H=2" in r["fallback"][0])
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    # the trunk alone is bit-identical; the 2x2 layers at 64 px cannot
    # split over 4 shards, and the engine's own warning says so
    assert "TRUNK_ERR 0.0" in out.stdout, out.stdout
    assert "HALOS True" in out.stdout, out.stdout
    assert "FALLBACK 1 True" in out.stdout, out.stdout
