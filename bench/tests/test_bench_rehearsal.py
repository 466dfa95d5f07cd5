"""Every cell's code path, end to end on the CPU at small sizes.

The measuring entry refuses anything but a TPU, so these tests call
``run.execute`` with the chip check off; everything else is a run.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, run
from bench.tests import smoke

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
ROOT = harness.ROOT


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return smoke.write(tmp_path_factory.mktemp("bench"))


def _run(manifest, cell, trace=0, seconds=1.5, seed=2**31 + 17):
    args = run.parse(["--workload", cell, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    return run.execute(args, manifest, require_tpu=False, cache=False)


@pytest.mark.parametrize("cell", ["darknet19.b8", "darknet19.stream1",
                                  "qwen2vl2b.decode", "qwen2vl2b.chat"])
def test_cell_runs_and_is_correct(manifest, cell):
    res = _run(manifest, cell)
    assert list(res) == KEYS
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in manifest.end_to_end(cell, {"setup_s"})}
    assert set(res["metrics"]) == want | {"setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)


@pytest.mark.parametrize("cell", ["darknet19.b8", "qwen2vl2b.decode"])
def test_traced_run_reports_layer_metrics(manifest, cell):
    res = _run(manifest, cell, trace=1)
    assert list(res) == KEYS[:5] + ["breakdown", "checks"]
    assert res["correct"] is True
    # the CPU has no TPU plane: device metrics stay out, never 0
    names = {m["name"] for m in manifest.per_layer(cell, set())}
    assert set(res["metrics"]) <= names
    for v in res["metrics"].values():
        assert v["value"] > 0
    assert {"busy_s", "window_s"} <= set(res["device"])


def test_command_refuses_a_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "darknet19.b8", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "darknet19.b8", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
