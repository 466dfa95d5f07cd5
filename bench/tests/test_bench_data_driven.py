"""A new configuration, traffic mix and per-layer metric need only new
files and new manifest entries: the harness finds them by name."""

import json

from bench import harness, run

TINY = {"system": "cnn", "reference": "darknet_ref.py", "model": "tiny_yolo",
        "input_size": 64, "head_anchors": 5, "head_classes": 20,
        "backbone": [[16, 3], "M", [32, 3], "M", [64, 3], "M", [128, 3], "M",
                     [256, 3], "M", [512, 3], "M", [1024, 3]],
        "head": [[512, 3]], "d_ratio": 4, "u_ratio": 4,
        "engine": "pallas_fused"}
MIX = {"loop": "closed", "clients": 1, "frames_per_request": 4,
       "frame_pool": 8, "check_requests": 2}
METRIC = '''"""Frames per request the traced cell submitted (a count)."""


def read(view):
    return float(view["batch"])
'''


def test_new_files_are_found_and_run(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "tiny_yolo_64.json").write_text(json.dumps(TINY))
    (tmp_path / "traffic" / "burst4.json").write_text(json.dumps(MIX))
    (tmp_path / "metrics" / "frames_per_request.py").write_text(METRIC)
    manifest = {
        "configs": [{"name": "tiny_yolo_64", "source": "test",
                     "file": "configs/tiny_yolo_64.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny.burst4", "config": "tiny_yolo_64",
                       "traffic": "burst4", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "images_per_s", "unit": "images/s", "better": "higher",
             "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny.burst4"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "frames_per_request", "unit": "frames",
             "better": "higher", "source": "program_counter",
             "layer": "front door", "moves": "images_per_s",
             "workloads": ["tiny.burst4"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    m = harness.Manifest(str(tmp_path / "BENCHMARK.json"),
                         search=(str(tmp_path),))
    for trace in (0, 1):
        args = run.parse(["--workload", "tiny.burst4", "--seed", "5",
                          "--seconds", "1", "--trace", str(trace)])
        res = run.execute(args, m, require_tpu=False, cache=False)
        assert res["correct"] is True, res["checks"]
        if trace:
            assert res["metrics"] == {
                "frames_per_request": {"value": 4.0, "unit": "frames"}}
        else:
            assert set(res["metrics"]) == {"images_per_s", "setup_s"}
