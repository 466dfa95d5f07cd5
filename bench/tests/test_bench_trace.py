"""The trace reduction and the per-layer readers, on traces recorded on a
TPU v5e (two DarkNet-19 batch-8 forwards; two qwen2-vl-2b decode steps)."""

import os

import jax
import jax.numpy as jnp
import pytest

from bench import harness, peaks, traceread
from bench.counts import conv, lm

DATA = os.path.join(os.path.dirname(__file__), "data")
MANIFEST = harness.Manifest()


def _trace(cell):
    return traceread.load(os.path.join(DATA, f"trace_{cell}.json.gz"))


def _read(metric, view):
    return MANIFEST.metric_reader(metric).read(view)


def _peaks():
    return peaks.peaks("TPU v5 lite")


def test_union_of_overlapping_intervals():
    ev = [traceread.Event("a", 0, 10), traceread.Event("b", 5, 20),
          traceread.Event("c", 30, 40)]
    assert traceread.union_seconds(ev) == pytest.approx(30e-9)


def test_op_names_are_the_hlo_instruction_names():
    assert traceread.op_name("%rebranch_conv.4 = f32[8,8]{1,0} custom-call("
                             "f32[8,8] %pad.43)") == "rebranch_conv.4"


def test_cnn_trace_has_one_kernel_per_site_per_forward():
    t = _trace("darknet19.b8")
    body = MANIFEST.config("darknet19_416")["body"]
    forwards = t.module_events("forward")
    assert len(forwards) == 2
    assert len(t.kernel_events("rebranch_conv")) == 2 * len(conv.sites(body))
    assert 0 < t.busy_s() <= t.window_s
    view = {"trace": t, "forward": conv.forward_work(body, 8),
            "peaks": _peaks}
    for metric in ("rebranch_conv_roofline", "mfu.cnn", "device_idle.cnn"):
        v = _read(metric, view)
        assert 0 < v <= 100, (metric, v)
    br = t.breakdown()
    assert 0 < len(br["device_ops"]) <= 10
    assert br["device_ops"][0][0].startswith("rebranch_conv")


def test_lm_trace_decode_readers():
    t = _trace("qwen2vl2b.decode")
    body = MANIFEST.config("qwen2_vl_2b")["body"]
    assert len(t.module_events("decode_step")) == 2
    view = {"trace": t, "peaks": _peaks, "body": body, "kv_itemsize": 4,
            "lm_counts": lm, "traced_steps": [[600] * 16] * 2,
            "traced_tokens": 32}
    for metric in ("decode_step_roofline", "mfu.decode",
                   "device_idle.decode"):
        v = _read(metric, view)
        assert 0 < v <= 100, (metric, v)
    # a new request's prefill chunk ran between the two steps
    assert _read("prefill_call_ms", view) > 0
    gaps = t.breakdown()["idle_gaps"]
    assert gaps and all(name.split()[0] in ("before", "inside")
                        for name, _ in gaps)


def test_readers_return_nothing_without_a_device():
    view = {"trace": None, "step_rows": [], "admit_wait_s": [],
            "ttft_s": [], "traced_steps": [], "traced_tokens": 0}
    for m in MANIFEST.data["per_layer"]:
        assert _read(m["name"], view) is None, m["name"]


def test_host_clock_readers():
    view = {"ttft_s": [0.5, 0.1, 2.0], "admit_wait_s": [0.2, 0.4]}
    assert _read("ttft_p50_ms", view) == pytest.approx(500.0)
    assert _read("admit_wait_ms_p50", view) == pytest.approx(300.0)


def test_tracer_round_trip_on_the_host(tmp_path):
    tr = traceread.Tracer(True, seconds=3.0, length=1.0)
    tr.tick(1.0)
    assert not tr.active
    tr.tick(2.0)
    assert tr.active
    jax.block_until_ready(jnp.ones(8) * 2)
    tr.tick(3.0)
    assert tr.active          # the loop stops it as the window closes
    t = tr.read()
    assert not tr.active and t.window_s > 0
    assert t.device == {} and t.busy_s() == 0.0     # the CPU has no TPU
    traceread.save(t, str(tmp_path / "t.json.gz"))
    assert traceread.load(str(tmp_path / "t.json.gz")).window_s == \
        t.window_s
