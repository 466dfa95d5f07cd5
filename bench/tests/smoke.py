"""Small copies of the benchmark's configurations and mixes, for tests.

``write(tmp)`` lays out a manifest in ``tmp`` whose cells are the real
ones, with the configurations cut to sizes the CPU runs in seconds
(DarkNet-19 at 32 px, the two-layer qwen2-vl smoke sizes) and the mixes
cut to match.  Everything else (systems, references, metric readers) is
the benchmark's own.
"""

from __future__ import annotations

import json
import os

from bench import harness

CNN = {"input_size": 32}
LM = {"name": "qwen2_vl_2b_smoke", "hidden_size": 64,
      "intermediate_size": 256, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 128,
      "dtype": "float32"}
LM_SERVING = {"rows": 4, "max_len": 64, "prefill_chunk": 8}
TRAFFIC = {
    "b8": {"frame_pool": 8, "check_requests": 2},
    "stream1": {"frame_pool": 8, "check_requests": 3},
    "decode": {"clients": 4, "size_pool": 64,
               "prompt_len": {"dist": "loguniform", "lo": 8, "hi": 24},
               "output_len": {"dist": "uniform", "lo": 4, "hi": 12}},
    "chat": {"rate_per_s": 8.0,
             "prompt_len": {"dist": "lognormal", "median": 10, "sigma": 1.0,
                            "lo": 4, "hi": 24},
             "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.8,
                            "lo": 2, "hi": 10}},
}


def _dump(path: str, data: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(data, f)


def write(tmp) -> harness.Manifest:
    tmp = str(tmp)
    bench = harness.Manifest()
    data = json.loads(json.dumps(bench.data))
    for c in data["configs"]:
        body = bench.config(c["name"])["body"]
        if body["system"] == "cnn":
            body.update(CNN)
        else:
            body.update(LM)
            body["serving"] = {**body["serving"], **LM_SERVING}
        c["file"] = f"configs/{c['name']}.json"
        _dump(os.path.join(tmp, c["file"]), body)
    for name, cut in TRAFFIC.items():
        _dump(os.path.join(tmp, "traffic", f"{name}.json"),
              {**bench.traffic(name), **cut})
    _dump(os.path.join(tmp, "BENCHMARK.json"), data)
    return harness.Manifest(os.path.join(tmp, "BENCHMARK.json"),
                            search=(tmp,))
