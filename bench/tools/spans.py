"""Read the serving path's host spans and named scopes on the chip.

    python3 bench/tools/spans.py --workload <cell> --seed <n> --seconds 50 \
        [--save PATH] [--cost-pairs 3 --cost-seconds 8]

One set-up, then one window as a traced benchmark run makes it (the
profiler on for its last 8 s), with the program's span recorder on from
the window's first tick (``spantrace.SpanTracer``).  It prints one JSON
line: the cell's accepted per-layer metrics and the span readers of
``bench/metrics/`` (those that find something to read), the main
program's device self time by scope and how much of it the HLO join
covers (``SpanTrace.coverage``), the alignment of each program
event with the spans that dispatched and waited for it, the idle time
under each span name, the spans open in the longest idle stretches and
in the trace's first gap, where a request's queue wait and the
benchmark's admission wait part, and a span's host cost (off and on)
with the spans recorded per second.

``--save`` writes a short stretch of that trace (``--save-seconds``,
from the first prefill or forward in it), spans and scopes included,
for the tests.  ``--cost-pairs`` then runs that many pairs of untraced
windows of ``--cost-seconds``, recorder off and on in turn (alternating
which goes first), and reports the cell's end-to-end metrics for each.
"""

from __future__ import annotations

import argparse
import bisect
import gzip
import json
import statistics

import jax

from common import build  # puts the checkout on sys.path

from bench import harness, peaks, spantrace, traceread

READERS = ("branch_share.decode", "attention_share.decode",
           "im2col_share.cnn", "copy_idle.cnn", "admit_idle.decode",
           "admit_idle.chat", "queue_wait_ms_p50", "kv_live_share.decode")


def program_text(s) -> tuple[str, str]:
    """The cell's main program and its compiled HLO text, lowered with
    arguments like those the window passed (an in-memory cache hit: no
    compile); ``SpanTrace.coverage`` checks that its op names are the
    ones that ran."""
    server = s.server
    if hasattr(server, "batcher"):
        b = server.batcher
        return "decode_step", b._decode.lower(
            b.params, jax.numpy.asarray(b._tok),
            b.pool.cache).compile().as_text()
    frames = jax.numpy.asarray(s.frames[:s.per])   # as submit() passes
    return "forward", server._forward.lower(
        s.params, frames).compile().as_text()


def quantiles(xs) -> list[float] | None:
    if len(xs) < 2:
        return None
    return [min(xs), *statistics.quantiles(xs, n=100)[0:99:49], max(xs)]


def split(trace, program) -> dict[str, float]:
    """Self-time share (%) of ``program`` by scope group."""
    by_path = trace.scope_seconds(program) or {}
    total = sum(by_path.values()) or 1.0
    out: dict[str, float] = {}
    for path, t in by_path.items():
        parts = spantrace.path_parts(path)
        comp = next((c for c in ("embed", "attention", "mlp", "lm_head",
                                 "patches") if c in parts), "other")
        part = next((c for c in ("kv_write", "branch", "trunk")
                     if c in parts), "")
        key = f"{comp}.{part}" if part else comp
        out[key] = out.get(key, 0.0) + 100.0 * t / total
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_intervals(trace) -> list[tuple[float, float]]:
    """The traced window's stretches in which no op ran."""
    w0, w1 = trace.window()
    busy = spantrace.merge((e.start, e.end) for e in trace.ops())
    return spantrace.merge(
        (max(a, w0), min(b, w1))
        for a, b in zip([w0] + [e for _, e in busy],
                        [s for s, _ in busy] + [w1]))


def longest_gaps(trace, n: int = 5) -> list[dict]:
    """The ``n`` longest idle stretches: ms, ms after the window's start,
    and the spans open in them (ms of overlap, by name)."""
    w0, _ = trace.window()
    out = []
    for a, b in sorted(idle_intervals(trace), key=lambda g: g[0] - g[1])[:n]:
        host = {}
        for s in trace.spans:
            o = spantrace.overlap([(a, b)], [(s["start"], s["end"])])
            if o:
                host[s["name"]] = host.get(s["name"], 0.0) + 1e-6 * o
        out.append({"ms": 1e-6 * (b - a), "at_ms": 1e-6 * (a - w0),
                    "spans_ms": host})
    return out


def idle_by_span(trace) -> dict:
    """Idle ms of the traced window under each span name, and with no
    span open."""
    idle = idle_intervals(trace)
    out = {}
    for name in sorted({s["name"] for s in trace.spans}):
        host = spantrace.merge((s["start"], s["end"])
                               for s in trace.named(name))
        out[name] = 1e-6 * spantrace.overlap(idle, host)
    every = spantrace.merge((s["start"], s["end"]) for s in trace.spans)
    out["(no span)"] = 1e-6 * (sum(b - a for a, b in idle)
                               - spantrace.overlap(idle, every))
    return out


def first_gap(trace) -> dict:
    """From the window's start to the first op: its length and the
    spans open in it (ms of overlap, by name)."""
    w0, _ = trace.window()
    ops = trace.ops()
    if not ops:
        return {}
    first = min(e.start for e in ops)
    gap = [(w0, first)]
    host = {}
    for s in trace.spans:
        o = spantrace.overlap(gap, [(s["start"], s["end"])])
        if o:
            host[s["name"]] = host.get(s["name"], 0.0) + 1e-6 * o
    return {"profile_start_to_first_op_ms": 1e-6 * first,
            "window_start_ms": 1e-6 * w0,
            "gap_ms": 1e-6 * (first - w0), "spans_ms": host}


def admission(trace, w) -> dict:
    """Where a request's queue wait (submit to first prefill dispatch)
    and the benchmark's admission wait (due time to the start of the
    admitting tick) part: the generator's lateness (due to submit) and
    the time from a tick's start to the dispatch inside it (ms, p50)."""
    steps = sorted(trace.named("batcher.step"), key=lambda s: s["start"])
    starts = [s["start"] for s in steps]
    in_tick = []
    for q in trace.named("request.queue"):
        i = bisect.bisect_right(starts, q["end"]) - 1
        if i >= 0 and q["end"] <= steps[i]["end"]:
            in_tick.append(1e-6 * (q["end"] - starts[i]))
    late = [1e3 * x for x in getattr(w, "late_s", ())]
    p50 = lambda xs: statistics.median(xs) if xs else None
    return {"late_p50": p50(late), "in_tick_p50": p50(in_tick),
            "in_tick_n": len(in_tick)}


def cut(trace, seconds: float) -> spantrace.SpanTrace:
    """``seconds`` of ``trace`` from just before its first prefill or
    forward, with the spans and scopes that fall in it."""
    w0, _ = trace.window()
    firsts = [s["start"] for s in trace.named("batcher.prefill",
                                              "cnn.forward")
              if s["start"] >= w0]
    t0 = min(firsts, default=w0 + 1e6) - 1e6
    t1 = t0 + seconds * 1e9
    keep = lambda e: t0 <= e.start and e.end <= t1
    device = {p: {k: [e for e in v if keep(e)] for k, v in lines.items()}
              for p, lines in trace.device.items()}
    spans = [s for s in trace.spans if s["end"] > t0 and s["start"] < t1]
    out = spantrace.SpanTrace(device, seconds, start_ns=trace.start_ns,
                              window_start_ns=t0, spans=spans,
                              offset_ns=trace.offset_ns)
    ran = {e.name for e in out.ops()}
    out.scopes = {p: {op: path for op, path in paths.items() if op in ran}
                  for p, paths in trace.scopes.items()}
    out.inherited = {p: [op for op in ops if op in ran]
                     for p, ops in trace.inherited.items()}
    return out


def cost(s, pairs: int, seconds: float) -> dict:
    """End-to-end metrics of untraced windows, recorder off and on."""
    from repro.serve import trace as recorder
    out = {"off": [], "on": []}
    for i in range(pairs):
        for on in ((False, True) if i % 2 == 0 else (True, False)):
            s.run.tracer = traceread.Tracer(False, seconds)
            if on:
                recorder.enable()
            w = s.window(seconds)
            recorder.disable()
            out["on" if on else "off"].append(
                {k: v for k, (v, _) in s.end_to_end(w).items()})
    return out


def span_cost(n: int = 100_000) -> dict:
    """Host ns of one ``with span(...)`` with the recorder off and on,
    on this machine's host."""
    import time
    from repro.serve import trace as recorder
    out = {}
    for on in (False, True):
        if on:
            recorder.enable()
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with recorder.span("batcher.decode", rows=1):
                pass
        out["on" if on else "off"] = (time.perf_counter_ns() - t0) / n
        recorder.disable()
    return out


def measure(s, seconds: float, save: str | None = None,
            save_seconds: float = 0.4, cost_pairs: int = 0,
            cost_seconds: float = 8.0) -> dict:
    """The readings of one window of the set-up system ``s``."""
    s.run.tracer = spantrace.SpanTracer(True, seconds)
    w = s.window(seconds)
    trace = s.run.tracer.read()
    program, text = program_text(s)
    trace.add_program(program, text)

    manifest, kind = s.run.manifest, jax.devices()[0].device_kind
    cell = s.run.cell["name"]
    view = s.layer_view(w)
    view.update(trace=trace, window=w, peaks=lambda: peaks.peaks(kind))
    reported = set(s.end_to_end(w)) | {"setup_s"}
    names = [m["name"] for m in manifest.per_layer(cell, reported)]
    metrics = {}
    for name in names + list(READERS):
        v = manifest.metric_reader(name).read(view)
        if v is not None:
            metrics[name] = v
    align = {p: {k: quantiles(v) for k, v in trace.alignment(p).items()}
             for p in spantrace.WAIT if trace.module_events(p)}
    out = {"workload": cell, "seed": s.run.seed,
           "device": harness.device_record(jax.devices()),
           "end_to_end": {k: v for k, (v, _) in s.end_to_end(w).items()},
           "metrics": metrics, "window_s": trace.window_s,
           "busy_s": trace.busy_s(), "offset_ns": trace.offset_ns,
           "spans": len(trace.spans), "dropped": trace.dropped,
           "alignment_ns": align, "split": split(trace, program),
           "coverage": trace.coverage(program),
           "idle_ms_by_span": idle_by_span(trace),
           "longest_gaps": longest_gaps(trace),
           "first_gap": first_gap(trace), "admission_ms": admission(trace, w)}
    if save:
        with gzip.open(save, "wt") as f:
            json.dump(cut(trace, save_seconds).to_json(), f)
    if cost_pairs:
        out["recorder_cost"] = cost(s, cost_pairs, cost_seconds)
    out["span_ns"] = span_cost()
    out["spans_per_s"] = len(trace.spans) / (w.t_end - w.t0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--save")
    ap.add_argument("--save-seconds", type=float, default=0.4)
    ap.add_argument("--cost-pairs", type=int, default=0)
    ap.add_argument("--cost-seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    # A cached executable keeps the metadata of the program it was first
    # compiled from; with metadata in the key, the one that runs carries
    # this checkout's scopes.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    s = build(args.workload, args.seed, args.seconds, trace=True)
    out = measure(s, args.seconds, args.save, args.save_seconds,
                  args.cost_pairs, args.cost_seconds)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
