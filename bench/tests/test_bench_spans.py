"""The span and scope readers (``bench/spantrace.py`` and their metrics):
exact on synthetic ops and spans, and on two short traces with spans
recorded on a TPU v5e (DarkNet-19 stream1, qwen2-vl-2b chat)."""

import json
import os
import sys

import pytest

from bench import harness, spantrace, traceread
from bench.tests import smoke

DATA = os.path.join(os.path.dirname(__file__), "data")
TOOLS = os.path.join(harness.BENCH_DIR, "tools")
MANIFEST = harness.Manifest()
E = traceread.Event
PLANE = "/device:TPU:0"


def _read(metric, view):
    return MANIFEST.metric_reader(metric).read(view)


def _span(name, start, end, rid=None, parent=-1, **attrs):
    return {"name": name, "start": start, "end": end, "parent": parent,
            "rid": rid, "attrs": attrs}


def _trace(ops, modules, spans=(), window=(0.0, 100.0), scopes=None):
    return spantrace.SpanTrace(
        {PLANE: {"ops": list(ops), "modules": list(modules)}},
        (window[1] - window[0]) * 1e-9, start_ns=0,
        window_start_ns=window[0], spans=list(spans), scopes=scopes)


def test_intervals_and_self_times():
    assert spantrace.merge([(5, 8), (0, 2), (1, 3), (8, 9)]) == \
        [(0, 3), (5, 9)]
    assert spantrace.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    outer, a, b = E("while", 0, 100), E("a", 10, 30), E("b", 40, 45)
    got = dict((e.name, t) for e, t in spantrace.self_times([b, outer, a]))
    assert got == {"while": 75, "a": 20, "b": 5}


def test_hlo_scopes_reads_metadata_and_fusion_roots():
    hlo = "\n".join([
        "%fused_computation.1 (p: f32[4]) -> f32[4] {",
        '  %c.1 = f32[4] convert(%p), metadata={op_name="jit(f)/mlp/branch'
        '/convert_element_type" stack_frame_id=2}',
        '  ROOT %m.2 = f32[4] multiply(%c.1, %c.1), metadata={op_name='
        '"jit(f)/mlp/branch/mul"}',
        "}",
        "ENTRY %main.3 (x: f32[4]) -> f32[4] {",
        '  %x = f32[4] parameter(0), metadata={op_name="x"}',
        "  %p.2 = f32[2,4] parameter(1), metadata={op_name="
        "\"params[\\'layers\\'][\\'attn\\'][\\'q\\'][\\'rom\\']"
        "[\\'C\\']\"}",
        "  %s.3 = ((f32[2,4]{1,0:T(8,128)}), f32[1,4], s32[]) "
        "slice-start(%p.2), slice={[0:1], [0:4]}",
        "  %s.4 = f32[1,4] slice-done(%s.3)",
        "  %convert.5 = bf16[1,4] convert(%s.4), backend_config={}",
        "  %fusion.1 = f32[4] fusion(%x), kind=kLoop, "
        "calls=%fused_computation.1",
        "  %constant.8 = f32[] constant(0)",
        '  ROOT %dot.7 = f32[4] dot(%fusion.1, %x), metadata={op_name='
        '"jit(f)/attention/trunk/dot_general"}',
        "}"])
    got, inherited = spantrace.hlo_scopes(hlo)
    assert got["fusion.1"] == "jit(f)/mlp/branch/mul"
    assert got["dot.7"] == "jit(f)/attention/trunk/dot_general"
    assert got["c.1"].endswith("convert_element_type")
    # a convert the compiler hoisted reads a parameter: its argument path
    assert got["convert.5"] == "params/layers/attention/q/rom/branch"
    assert got["constant.8"] == "" and len(got) == 10
    assert inherited == {"s.3", "s.4", "convert.5"}
    assert spantrace.path_parts("jit(f)/reshape;patches/reshape") == \
        ["jit(f)", "reshape", "patches", "reshape"]


def test_spans_go_on_the_trace_clock_with_the_dispatch_offset():
    base = traceread.Trace({PLANE: {
        "ops": [E("fusion.1", 2_000, 3_000)],
        "modules": [E("jit_forward(7)", 2_000, 3_000)]}}, 1e-5)
    # anchor: perf 1_000 is wall 50_000; the trace starts at wall 40_000,
    # so perf p sits at p + 9_000 on the trace clock
    snap = {"anchor": [50_000, 1_000], "dropped": 2, "spans": [
        _span("cnn.forward", -6_500, -6_400),        # trace 2_500..2_600
        _span("cnn.copy_out", -6_350, -5_800),       # trace 2_650..3_200
        _span("request.queue", -8_000, 0)]}
    t = spantrace.SpanTrace.from_snapshot(base, 40_000, 41_000, snap)
    # the event began 500 ns before its dispatch: spans move 500 earlier
    assert t.offset_ns == 500
    assert [(s["start"], s["end"]) for s in t.spans] == \
        [(2_000, 2_100), (2_150, 2_700), (500, 8_500)]
    assert t.window() == (1_000, 11_000) and t.dropped == 2
    assert t.alignment("forward") == {"start_lag": [0], "end_lead": [-300]}


def test_idle_while_and_scope_shares_are_exact():
    ops = [E("fusion.1", 10, 30), E("while", 50, 90), E("dot.2", 60, 70),
           E("scatter.3", 95, 97)]
    mods = [E("jit_decode_step(1)", 50, 90), E("jit_prefill(2)", 10, 30)]
    spans = [_span("batcher.admit", 0, 20), _span("batcher.admit", 35, 55),
             _span("batcher.decode", 45, 50, rows=3, kv_live=30,
                   kv_positions=120),
             _span("batcher.decode", 91, 94, rows=2, kv_live=60,
                   kv_positions=120),
             _span("request.queue", 0, 4e6), _span("request.queue", 0, 2e6),
             _span("request.queue", 0, 9e6)]
    scopes = {"decode_step": {"while": "jit(decode_step)/while",
                              "dot.2": "jit(decode_step)/while/body/"
                                       "attention/branch/dot_general"}}
    t = _trace(ops, mods, spans, scopes=scopes)
    # idle inside admit: [0,10] and [35,50]
    assert t.idle_while("batcher.admit") == pytest.approx(25.0)
    view = {"trace": t}
    assert _read("admit_idle.decode", view) == pytest.approx(25.0)
    assert _read("admit_idle.chat", view) == pytest.approx(25.0)
    # decode_step self time: while 30, dot 10 (branch, in attention)
    assert _read("branch_share.decode", view) == pytest.approx(25.0)
    assert _read("attention_share.decode", view) == 0.0
    assert t.scope_share("decode_step", "attention") == pytest.approx(25.0)
    assert _read("queue_wait_ms_p50", view) == pytest.approx(4.0)
    assert _read("kv_live_share.decode", view) == pytest.approx(37.5)
    assert t.scope_share("forward", "patches") is None


def test_coverage_splits_the_program_time_by_how_ops_got_a_path():
    ops = [E("fusion.1", 0, 10), E("copy.2", 10, 30), E("convert.3", 30, 40),
           E("fusion.4", 40, 60)]
    mods = [E("jit_decode_step(1)", 0, 60)]
    scopes = {"decode_step": {"fusion.1": "jit(decode_step)/mlp/trunk/dot",
                              "copy.2": "",
                              "convert.3": "params/layers/mlp/up/rom/branch"}}
    t = _trace(ops, mods, scopes=scopes)
    t.inherited = {"decode_step": ["convert.3"]}
    got = t.coverage("decode_step")
    # fusion.4 is not in the HLO text's names: its 20 go to no scope
    assert got == pytest.approx({"missing": 100 * 20 / 60,
                                 "unscoped": 100 * 20 / 60,
                                 "operand": 100 * 10 / 60})
    assert t.coverage("forward") is None


def test_cnn_readers_are_exact():
    ops = [E("fusion.1", 20, 40), E("rebranch_conv.2", 40, 70),
           E("fusion.3", 70, 80)]
    mods = [E("jit_forward(1)", 20, 80)]
    spans = [_span("cnn.copy_in", 5, 25), _span("cnn.forward", 18, 19),
             _span("cnn.copy_out", 75, 95)]
    scopes = {"forward": {"fusion.1": "jit(forward)/patches/concatenate",
                          "fusion.3": "jit(forward)/branch/dot_general"}}
    view = {"trace": _trace(ops, mods, spans, scopes=scopes)}
    # idle inside copies: [5,20] and [80,95]
    assert _read("copy_idle.cnn", view) == pytest.approx(30.0)
    assert _read("im2col_share.cnn", view) == pytest.approx(100 * 20 / 60)
    assert _read("copy_idle.cnn", view) <= _read("device_idle.cnn", view)


@pytest.mark.parametrize("metric", [
    "branch_share.decode", "attention_share.decode", "im2col_share.cnn",
    "copy_idle.cnn", "admit_idle.decode", "admit_idle.chat",
    "queue_wait_ms_p50", "kv_live_share.decode"])
def test_readers_need_spans_or_scopes(metric):
    plain = traceread.load(os.path.join(DATA,
                                        "trace_qwen2vl2b.decode.json.gz"))
    for trace in (None, plain, _trace([], [])):
        assert _read(metric, {"trace": trace}) is None


@pytest.mark.parametrize("cell", ["darknet19.b8", "qwen2vl2b.decode"])
def test_recorded_traces_load_either_way(cell):
    path = os.path.join(DATA, f"trace_{cell}.json.gz")
    plain, spans = traceread.load(path), spantrace.load(path)
    assert spans.spans == [] and spans.scopes == {}
    assert spans.to_json()["device"] == plain.to_json()["device"]
    assert spans.busy_s() == plain.busy_s()
    assert spans.breakdown() == plain.breakdown()
    for m in MANIFEST.data["per_layer"]:
        if m["name"].startswith(("device_idle", "prefill_call_ms")):
            assert _read(m["name"], {"trace": spans}) == \
                _read(m["name"], {"trace": plain}), m["name"]


def test_span_tracer_turns_the_recorder_on_for_the_window(tmp_path):
    import jax.numpy as jnp
    from repro.serve import trace as recorder
    tr = spantrace.SpanTracer(True, seconds=2.0, length=1.0)
    tr.tick(0.0)
    assert recorder.enabled() and not tr.active
    with recorder.span("cnn.request"):
        tr.tick(1.5)
        (jnp.ones(8) * 2).block_until_ready()
    assert tr.active
    t = tr.read()
    assert not recorder.enabled()
    assert [s["name"] for s in t.spans] == ["cnn.request"]
    assert t.window_start_ns > 0 and t.window_s > 0
    path = tmp_path / "t.json.gz"
    traceread.save(t, str(path))
    back = spantrace.load(str(path))
    assert back.spans == t.spans and back.start_ns == t.start_ns
    assert traceread.load(str(path)).window_s == t.window_s


@pytest.fixture(scope="module")
def tool():
    if TOOLS not in sys.path:
        sys.path.insert(0, TOOLS)
    return harness.load_module(os.path.join(TOOLS, "spans.py"),
                               "_bench_tool_spans")


@pytest.mark.parametrize("cell", ["darknet19.stream1", "qwen2vl2b.chat"])
def test_span_tool_on_the_host(tmp_path, tool, cell):
    """The span tool end to end at small sizes (no device plane on the
    CPU, so only the host's readers report)."""
    import common
    m = smoke.write(tmp_path / "bench")
    s = common.build(cell, 2**31 + 11, 1.0, manifest=m, require_tpu=False,
                     cache=False, trace=True)
    out = tool.measure(s, 1.0, save=str(tmp_path / "cut.json.gz"),
                       save_seconds=0.2, cost_pairs=1, cost_seconds=0.3)
    common.free(s)
    json.dumps(out)
    assert out["spans"] > 0 and out["dropped"] == 0
    assert len(out["recorder_cost"]["on"]) == 1
    if cell == "qwen2vl2b.chat":
        assert out["metrics"]["queue_wait_ms_p50"] > 0
        assert 0 < out["metrics"]["kv_live_share.decode"] <= 100
    cut = spantrace.load(str(tmp_path / "cut.json.gz"))
    assert cut.spans and cut.window_s == pytest.approx(0.2)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e (the TPU compiler, nothing runs)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_hoisted_converts_of_c_are_scoped_branch(one_chip):
    """In a ``decode_step`` compiled for the v5e, the compiler hoists the
    convert of each stacked float32 ``C`` out of the layers' loop with
    no metadata; it takes the ``branch`` scope from the parameter it
    reads, and counts as scoped through an operand."""
    import jax
    import jax.numpy as jnp
    from repro import serve
    model, _ = serve.compile_entry("qwen2-vl-2b-smoke")
    on_chip = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = jax.eval_shape(model.init, jax.random.key(0))
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        2, 13, 8, 48, dtype=jnp.float32))
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32, sharding=one_chip)
    text = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        on_chip(params), tok, on_chip(cache)).compile().as_text()
    paths, inherited = spantrace.hlo_scopes(text)
    first, param = {}, {}                  # op -> its first operand
    for line in text.splitlines():
        m = spantrace._INSTR.match(line)
        if m:
            name, rest = m.groups()
            first[name] = (spantrace._OPERAND.findall(rest) or [None])[0]
            meta = spantrace._OP_NAME.search(rest)
            if " parameter(" in rest:
                param[name] = meta.group(1) if meta else ""
    hoisted = []
    for name in inherited:
        op = first[name]
        while op in first and op not in param:
            op = first[op]
        if name.startswith("convert") and \
                param.get(op, "").replace("\\", "").endswith("['C']"):
            hoisted.append(name)
    assert len(hoisted) >= 7, hoisted      # one per ROM site of a layer
    for name in hoisted:
        assert "branch" in spantrace.path_parts(paths[name]), paths[name]


def _uncorrected(t):
    """``t`` with its spans where the recorder's anchor and the trace's
    start put them, before the trace's own offset."""
    data = t.to_json()
    for s in data["spans"]:
        s["start"] += t.offset_ns
        s["end"] += t.offset_ns
    data["offset_ns"] = 0.0
    return spantrace.SpanTrace.from_json(data)


@pytest.mark.parametrize("cell, program", [("darknet19.stream1", "forward"),
                                           ("qwen2vl2b.chat", "decode_step")])
def test_recorded_spans_line_up_with_the_device(cell, program):
    """Placed by the anchor and the trace's start alone, at least 99 % of
    the program events start no earlier than 1 ms before the span that
    dispatched them.  An offset fitted on the first half of the events
    (in time) puts at least 99 % of the second half within 0.1 ms of
    their dispatch; after the trace's own offset, at least 99 % of the
    events end no later than 0.1 ms after the span in which the host
    waits for them ends."""
    t = spantrace.load(os.path.join(DATA, f"trace_{cell}_spans.json.gz"))
    raw = _uncorrected(t)
    lags = sorted((e.start, e.start - s["start"])
                  for p in spantrace.DISPATCH
                  for s, e in raw.dispatch_pairs(p))
    assert len(lags) >= 10
    assert sum(lag >= -1e6 for _, lag in lags) >= 0.99 * len(lags), lags
    fit, held = lags[:len(lags) // 2], lags[len(lags) // 2:]
    offset = max(0.0, -min(lag for _, lag in fit))
    assert sum(lag + offset >= -1e5 for _, lag in held) >= \
        0.99 * len(held), (offset, held)
    ends = t.alignment(program)["end_lead"]
    assert len(ends) >= 3
    assert sum(x >= -1e5 for x in ends) >= 0.99 * len(ends), ends


def test_recorded_scopes_cover_the_programs():
    """Every op the device ran in the main program is named in the HLO
    text its scopes came from."""
    for cell, program in (("darknet19.stream1", "forward"),
                          ("qwen2vl2b.chat", "decode_step")):
        t = spantrace.load(os.path.join(DATA,
                                        f"trace_{cell}_spans.json.gz"))
        got = t.coverage(program)
        assert got["missing"] < 0.1, (cell, got)
        assert 0 <= got["unscoped"] < 100 and 0 <= got["operand"] < 100


def test_recorded_span_traces_feed_the_readers():
    cnn = {"trace": spantrace.load(os.path.join(
        DATA, "trace_darknet19.stream1_spans.json.gz"))}
    assert 0 < _read("copy_idle.cnn", cnn) <= _read("device_idle.cnn", cnn)
    assert 0 < _read("im2col_share.cnn", cnn) < 100
    lm = {"trace": spantrace.load(os.path.join(
        DATA, "trace_qwen2vl2b.chat_spans.json.gz"))}
    assert 0 <= _read("admit_idle.chat", lm) <= _read("device_idle.chat", lm)
    branch = _read("branch_share.decode", lm)
    attention = _read("attention_share.decode", lm)
    assert 0 < branch and 0 < attention and branch + attention <= 100
    assert 0 < _read("kv_live_share.decode", lm) <= 100
