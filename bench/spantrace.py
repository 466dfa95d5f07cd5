"""The serving path's host spans and named scopes, on the device trace's
clock.

``repro.serve.trace`` keeps the program's host spans in memory, stamped
with ``perf_counter_ns`` and one ``(time.time_ns(), perf_counter_ns())``
anchor.  JAX's profiler writes every event of a trace relative to the
session's start, ``profile_start_time`` (wall-clock ns) on the xplane's
``Task Environment`` plane.  So a span lies on the trace's clock at

    perf_ns + (anchor_wall - anchor_perf) - start_ns

less one correction: on a v5e the device's events can come out up to
about a millisecond earlier than the host stamps of the calls that
dispatched them.  ``SpanTrace`` measures that offset from the trace itself, as the
least shift that puts every program event at or after the span that
dispatched it (``DISPATCH``), and moves the spans by it; the events keep
their times, so the accepted readers read what they read before.

The device's op events carry no metadata.  A scope path comes from the
compiled program's HLO text (``metadata={op_name="jit(decode_step)/
while/body/attention/kv_write/scatter"}``), keyed by program and op,
because op names repeat across programs.  ``SpanTrace.coverage`` says
how far that join holds: the share of the program's device time in ops
the HLO text does not name, in ops it gives no path, and in ops whose
path came from an operand.

The benchmark's tracer keeps the host tracer off, so these spans are
never in the profiler's own trace.  ``SpanTracer`` is that tracer with
the recorder on from the window's first tick to its close.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import shutil
import time

from bench import traceread

ENV_PLANE = "Task Environment"
# program -> the span inside which the host dispatches it
DISPATCH = {"forward": "cnn.forward", "decode_step": "batcher.decode",
            "prefill": "batcher.prefill"}
# program -> the span after the dispatch in which the host waits for it
WAIT = {"forward": "cnn.copy_out", "decode_step": "batcher.sample"}
# how much earlier than its dispatch a device event may show (the
# offset of a v5e trace's device events from the host stamps: 0-1.0 ms)
MATCH_NS = 2e6

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=(.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_ARG_KEY = re.compile(r"\['([^']*)'\]|\[(\d+)\]")
# keys of the params tree -> the scope of the code that reads them
ARG_SCOPES = {"attn": "attention", "w_q": "trunk", "w_scale": "trunk",
              "C": "branch", "U": "branch", "core": "branch"}
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")


def start_ns(xplane_path: str) -> int:
    """``profile_start_time`` of the trace at ``xplane_path``."""
    from jax.profiler import ProfileData
    env = ProfileData.from_file(xplane_path).find_plane_with_name(ENV_PLANE)
    if env is None:
        raise ValueError(f"no {ENV_PLANE!r} plane in {xplane_path}")
    return int(dict(env.stats)["profile_start_time"])


def hlo_scopes(hlo_text: str) -> tuple[dict[str, str], set[str]]:
    """The scope path of every instruction of a compiled HLO module (""
    where none is found), and the instructions whose path came from an
    operand.  A path is the ``op_name`` metadata.  A fusion without
    metadata of its own takes its fused computation's root's.  An op
    with none at all (one the compiler made, such as a convert of a
    stacked parameter hoisted out of the layers' loop) takes its nearest
    operand's; a parameter's is its argument path, turned into scope
    names by ``ARG_SCOPES`` (``params['layers']['mlp']['up']['rom']['C']``
    -> ``params/layers/mlp/up/rom/branch``)."""
    own, calls, operands, root = {}, {}, {}, {}
    names, comps, comp = [], set(), None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            comps.add(comp)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        names.append(name)
        meta = _OP_NAME.search(rest)
        if meta:
            own[name] = _arg_path(meta.group(1).replace("\\", ""))
            if line.lstrip().startswith("ROOT") and comp is not None:
                root[comp] = own[name]
            continue
        called = _CALLS.search(rest)
        if called:
            calls[name] = called.group(1)
        operands[name] = _OPERAND.findall(rest)    # computations too
    for name, c in calls.items():
        if c in root:
            own[name] = root[c]
    inherited = set()
    for name, ops in operands.items():
        seen, todo = set(), list(ops)
        while todo and name not in own and len(seen) < 64:
            op = todo.pop(0)
            if op in seen or op in comps:
                continue
            seen.add(op)
            if op in own:
                own[name] = own[op]
                inherited.add(name)
            else:
                todo.extend(operands.get(op, ()))
    return {n: own.get(n, "") for n in names}, inherited


def _arg_path(op_name: str) -> str:
    """An argument's ``params['a'][0]['b']`` as ``params/a/0/b``, with
    the params tree's keys named as the scopes that use them; any other
    op name as it is."""
    head = op_name.split("[", 1)[0]
    if "/" in op_name or head == op_name or not head.isidentifier():
        return op_name
    keys = [a or b for a, b in _ARG_KEY.findall(op_name)]
    return "/".join([head] + [ARG_SCOPES.get(k, k) for k in keys])


def path_parts(path: str) -> list[str]:
    """The scope names of a path; a fused op's metadata may join the
    names of the ops it fuses with ``;``."""
    return re.split(r"[/;]", path)


def merge(intervals) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def self_times(events) -> list[tuple[traceread.Event, float]]:
    """Each event with its self time (ns): its length less that of the
    events nested directly inside it on the same line."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            out.append(tuple(stack.pop()))
        if stack and e.end <= stack[-1][0].end:
            stack[-1][1] -= e.end - e.start
        stack.append([e, e.end - e.start])
    out.extend(tuple(s) for s in reversed(stack))
    return out


class SpanTrace(traceread.Trace):
    """A :class:`traceread.Trace` plus the trace's start, the recorder's
    spans on its clock, and the scope path of each op per program."""

    def __init__(self, device: dict, window_s: float, *,
                 start_ns: int | None = None, window_start_ns: float = 0.0,
                 spans: list | None = None, scopes: dict | None = None,
                 inherited: dict | None = None, offset_ns: float = 0.0,
                 dropped: int = 0):
        super().__init__(device, window_s)
        self.start_ns = start_ns
        self.window_start_ns = window_start_ns
        self.spans = spans or []
        self.scopes = scopes or {}
        self.inherited = inherited or {}
        self.offset_ns = offset_ns
        self.dropped = dropped

    # -- building -----------------------------------------------------------
    @classmethod
    def from_snapshot(cls, base: traceread.Trace, start: int,
                      window_wall_ns: int, snap: dict) -> "SpanTrace":
        """``base`` with the recorder's ``snap`` placed on its clock."""
        wall, perf = snap["anchor"]
        shift = wall - perf - start
        spans = [{**s, "start": s["start"] + shift,
                  "end": max(s["end"], s["start"]) + shift}
                 for s in snap["spans"]]
        t = cls(base.device, base.window_s, start_ns=start,
                window_start_ns=window_wall_ns - start, spans=spans,
                dropped=snap["dropped"])
        t.offset_ns = t.dispatch_offset()
        for s in t.spans:
            s["start"] -= t.offset_ns
            s["end"] -= t.offset_ns
        return t

    def add_program(self, program: str, hlo_text: str) -> None:
        """Keep the scope paths of the ops of ``program`` that ran, and
        which of them took their path from an operand."""
        paths, inherited = hlo_scopes(hlo_text)
        ran = {e.name for m in self.module_events(program)
               for e in self.ops_in([m])}
        self.scopes[program] = {op: paths[op] for op in sorted(ran)
                                if op in paths}
        self.inherited[program] = sorted(ran & inherited)

    def to_json(self) -> dict:
        return {**super().to_json(), "start_ns": self.start_ns,
                "window_start_ns": self.window_start_ns,
                "offset_ns": self.offset_ns, "dropped": self.dropped,
                "spans": self.spans, "scopes": self.scopes,
                "inherited": self.inherited}

    @classmethod
    def from_json(cls, data: dict) -> "SpanTrace":
        base = traceread.Trace.from_json(data)
        return cls(base.device, base.window_s,
                   start_ns=data.get("start_ns"),
                   window_start_ns=data.get("window_start_ns", 0.0),
                   spans=data.get("spans"), scopes=data.get("scopes"),
                   inherited=data.get("inherited"),
                   offset_ns=data.get("offset_ns", 0.0),
                   dropped=data.get("dropped", 0))

    # -- reductions -----------------------------------------------------------
    def named(self, *names: str) -> list[dict]:
        return [s for s in self.spans if s["name"] in names]

    def window(self) -> tuple[float, float]:
        return (self.window_start_ns,
                self.window_start_ns + self.window_s * 1e9)

    def dispatch_pairs(self, program: str) -> list[tuple[dict, object]]:
        """Each event of ``program`` with the span that dispatched it:
        the last such span to start before the event does (give or take
        ``MATCH_NS``), each span once.  The event may start much later,
        behind the device's earlier work."""
        spans = sorted(self.named(DISPATCH[program]),
                       key=lambda s: s["start"])
        starts = [s["start"] for s in spans]
        pairs, used = [], set()
        for e in sorted(self.module_events(program), key=lambda e: e.start):
            i = bisect.bisect_right(starts, e.start + MATCH_NS) - 1
            if i >= 0 and i not in used:
                used.add(i)
                pairs.append((spans[i], e))
        return pairs

    def dispatch_offset(self) -> float:
        """The least shift (ns, >= 0) of the spans toward earlier times
        that puts every program event at or after its dispatching span."""
        lags = [e.start - s["start"]
                for p in DISPATCH for s, e in self.dispatch_pairs(p)]
        return max(0.0, -min(lags)) if lags else 0.0

    def alignment(self, program: str) -> dict[str, list[float]]:
        """Per event of ``program`` (ns): how long after its dispatching
        span began it started, and how long before the end of the span
        in which the host then waits for it (``WAIT``) it ended."""
        waits = sorted(self.named(WAIT[program]), key=lambda s: s["start"])
        starts = [s["start"] for s in waits]
        out = {"start_lag": [], "end_lead": []}
        for s, e in self.dispatch_pairs(program):
            out["start_lag"].append(e.start - s["start"])
            i = bisect.bisect_left(starts, s["end"])
            if i < len(waits):
                out["end_lead"].append(waits[i]["end"] - e.end)
        return out

    def ops_in(self, modules) -> list:
        """Op events that start inside one of ``modules``' events."""
        out = []
        for p in self.planes():
            ops = sorted(self.ops(p), key=lambda e: e.start)
            starts = [e.start for e in ops]
            for m in modules:
                lo = bisect.bisect_left(starts, m.start)
                hi = bisect.bisect_right(starts, m.end)
                out.extend(e for e in ops[lo:hi] if e.end <= m.end)
        return out

    def idle_while(self, *names: str) -> float | None:
        """Share (%) of the traced window in which no operation ran on
        the chip while the host was inside a span named ``names``,
        averaged over the chips; None without such spans."""
        spans = self.named(*names)
        if not spans or not self.device:
            return None
        w0, w1 = self.window()
        host = merge((max(s["start"], w0), min(s["end"], w1))
                     for s in spans)
        shares = []
        for p in self.planes():
            busy = merge((e.start, e.end) for e in self.ops(p))
            inside = sum(e - s for s, e in host)
            shares.append(inside - overlap(host, busy))
        return 100.0 * (sum(shares) / len(shares)) / (w1 - w0)

    def scope_seconds(self, program: str) -> dict[str, float] | None:
        """Device self time (s) of ``program``'s ops by scope path
        ("" where the op has none); None without the program's scopes."""
        paths = self.scopes.get(program)
        if not paths:
            return None
        out: dict[str, float] = {}
        for e, t in self_times(self.ops_in(self.module_events(program))):
            key = paths.get(e.name, "")
            out[key] = out.get(key, 0.0) + t * 1e-9
        return out

    def coverage(self, program: str) -> dict[str, float] | None:
        """Shares (%) of ``program``'s device self time in ops that its
        HLO text does not name (``missing``), that it names with no
        path (``unscoped``), and whose path came from an operand
        (``operand``); None without the program's scopes."""
        paths = self.scopes.get(program)
        if not paths:
            return None
        inherited = set(self.inherited.get(program, ()))
        out = dict.fromkeys(("missing", "unscoped", "operand"), 0.0)
        total = 0.0
        for e, t in self_times(self.ops_in(self.module_events(program))):
            total += t
            if e.name not in paths:
                out["missing"] += t
            elif not paths[e.name]:
                out["unscoped"] += t
            elif e.name in inherited:
                out["operand"] += t
        return {k: 100.0 * v / total for k, v in out.items()} if total \
            else None

    def scope_share(self, program: str, scope: str,
                    without: str | None = None) -> float | None:
        """Share (%) of ``program``'s device self time in ops whose path
        holds ``scope`` (and not ``without``)."""
        by_path = self.scope_seconds(program)
        if not by_path:
            return None
        total = sum(by_path.values())
        hit = sum(t for path, t in by_path.items()
                  if scope in path_parts(path)
                  and (without is None or without not in path_parts(path)))
        return 100.0 * hit / total if total else None


class SpanTracer(traceread.Tracer):
    """The benchmark's tracer with the recorder on: from the window's
    first tick to ``stop()``; ``read()`` returns a :class:`SpanTrace`."""

    def __init__(self, enabled: bool, seconds: float, length: float = 8.0):
        super().__init__(enabled, seconds, length)
        self._wall0 = None

    def tick(self, elapsed: float) -> None:
        from repro.serve import trace as recorder
        if self.enabled and not self.done and not recorder.enabled():
            recorder.enable()
        was = self.active
        super().tick(elapsed)
        if self.active and not was:
            self._wall0 = time.time_ns()

    def stop(self) -> None:
        from repro.serve import trace as recorder
        super().stop()
        recorder.disable()

    def read(self) -> SpanTrace | None:
        from repro.serve import trace as recorder
        self.stop()
        if self._dir is None:
            return None
        try:
            found = sorted(glob.glob(os.path.join(
                self._dir, "**", "*.xplane.pb"), recursive=True))
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {self._dir}")
            base = traceread.Trace.from_xspace(found[-1], self.window_s)
            return SpanTrace.from_snapshot(base, start_ns(found[-1]),
                                           self._wall0, recorder.snapshot())
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def load(path: str) -> SpanTrace:
    with gzip.open(path, "rt") as f:
        return SpanTrace.from_json(json.load(f))
