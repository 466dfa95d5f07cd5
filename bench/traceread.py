"""Reading the profiler's trace: device busy time, program and kernel time.

JAX's profiler writes an ``.xplane.pb`` under ``<dir>/plugins/profile/``.
On a TPU each chip is a plane named ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per operation the chip ran, and its line
``XLA Modules`` one event per program (``jit_<name>(<id>)``).  An
operation's event is named by its HLO text (``%rebranch_conv.4 = f32[...]
custom-call(...)``); the reduction keeps the operation's own name
(``rebranch_conv.4``): a Pallas kernel is named after its kernel
function.

The host tracer is off: recording the runtime's host events (one per
tile of every host-to-device layout transpose) slowed a DarkNet-19
batch-8 request from 63 ms to 230 ms on a v5e (PR 14), which would make
the traced device look idle.  Idle gaps are named instead by the program
the device ran next (``before jit_forward``) or ran around them
(``inside jit_decode_step``).

The reduction keeps only those lines, so a :class:`Trace` can be saved
as a small JSON file and read back (the tests read one recorded on the
chip).
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import shutil
import tempfile
import time
from typing import NamedTuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    name: str
    start: float            # ns, on the trace's clock
    end: float

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


def op_name(hlo_text: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def union_seconds(events: list[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for e in sorted(events, key=lambda e: e.start):
        if cur_e is None or e.start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start, e.end
        else:
            cur_e = max(cur_e, e.end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total * 1e-9


class Trace:
    """The device lines of one trace."""

    def __init__(self, device: dict, window_s: float):
        self.device = device        # plane -> {"ops": [...], "modules": [...]}
        self.window_s = window_s

    # -- loading ------------------------------------------------------------
    @classmethod
    def from_xspace(cls, path: str, window_s: float) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        device = {}
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                lines = {"ops": [], "modules": []}
                for line in plane.lines:
                    key = {OPS_LINE: "ops",
                           MODULES_LINE: "modules"}.get(line.name)
                    if key is None:
                        continue
                    name = op_name if key == "ops" else str
                    lines[key] = [Event(name(e.name), e.start_ns, e.end_ns)
                                  for e in line.events]
                device[plane.name] = lines
        return cls(device, window_s)

    @classmethod
    def from_dir(cls, directory: str, window_s: float) -> "Trace":
        found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {directory}")
        return cls.from_xspace(sorted(found)[-1], window_s)

    def to_json(self) -> dict:
        enc = lambda evs: [[e.name, e.start, e.end] for e in evs]
        return {"window_s": self.window_s,
                "device": {p: {k: enc(v) for k, v in lines.items()}
                           for p, lines in self.device.items()}}

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        dec = lambda evs: [Event(n, s, e) for n, s, e in evs]
        return cls({p: {k: dec(v) for k, v in lines.items()}
                    for p, lines in data["device"].items()},
                   data["window_s"])

    # -- reductions -----------------------------------------------------------
    def planes(self) -> list[str]:
        return sorted(self.device)

    def ops(self, plane: str | None = None) -> list[Event]:
        planes = [plane] if plane else self.planes()
        return [e for p in planes for e in self.device[p]["ops"]]

    def modules(self, plane: str | None = None) -> list[Event]:
        planes = [plane] if plane else self.planes()
        return [e for p in planes for e in self.device[p]["modules"]]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.device:
            return 0.0
        return sum(union_seconds(self.ops(p)) for p in self.planes()) \
            / len(self.device)

    def module_events(self, program: str) -> list[Event]:
        """Events of the jitted program ``program`` (``jit_<program>``),
        on every chip."""
        want = f"jit_{program}"
        return [e for e in self.modules()
                if e.name == want or e.name.startswith(want + "(")]

    def kernel_events(self, kernel: str) -> list[Event]:
        """Events of the operations named ``kernel`` (``kernel.<n>``)."""
        return [e for e in self.ops()
                if e.name == kernel or e.name.startswith(kernel + ".")]

    def breakdown(self, n: int = 10) -> dict:
        """The device operations that took most time, and the longest
        idle gaps, each named by the program around it or after it."""
        by_op: dict[str, float] = {}
        for e in self.ops():
            by_op[e.name] = by_op.get(e.name, 0.0) + e.seconds
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:n]
        gaps = []
        for p in self.planes():
            mods = sorted(self.modules(p), key=lambda e: e.start)
            end = None
            for e in sorted(self.ops(p), key=lambda e: e.start):
                if end is not None and e.start > end:
                    gaps.append((e.start - end, _gap_name(mods, end,
                                                          e.start)))
                end = e.end if end is None else max(end, e.end)
        gaps.sort(key=lambda g: -g[0])
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[name, d * 1e-9] for d, name in gaps[:n]]}


def _program(mods: list[Event], t: float) -> Event | None:
    """The program event that runs at time ``t``, if any."""
    i = bisect.bisect_right([m.start for m in mods], t) - 1
    return mods[i] if i >= 0 and mods[i].end >= t else None


def _gap_name(mods: list[Event], start: float, end: float) -> str:
    before, after = _program(mods, start), _program(mods, end)
    name = lambda m: m.name.split("(")[0]
    if after is None:
        return "before no program"
    if before is after:
        return f"inside {name(after)}"
    return f"before {name(after)}"


class Tracer:
    """Traces the last stretch of the measured window, in a run of its own.

    ``tick(elapsed)`` is called by the serving loop with the seconds
    since the window opened; the profiler starts at ``seconds - length``
    and stops when the serving loop calls ``stop()`` as the window closes:
    writing the trace out takes seconds on a TPU (PR 14: 13 s for 8 s of
    the chat cell), and inside the window it would stall the traffic.
    Off (``enabled=False``) it does nothing."""

    def __init__(self, enabled: bool, seconds: float,
                 length: float = 8.0):
        self.enabled = enabled
        self.offset = max(0.0, seconds - length)
        self.active = False
        self.done = False
        self._dir = None
        self._t0 = None
        self.window_s = None

    def tick(self, elapsed: float) -> None:
        if not self.enabled or self.done:
            return
        if not self.active and elapsed >= self.offset:
            import jax
            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 0        # see the module docstring
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._t0 = time.perf_counter()
            self.active = True

    def stop(self) -> None:
        if self.active:
            import jax
            self.window_s = time.perf_counter() - self._t0
            jax.profiler.stop_trace()
            self.active = False
            self.done = True

    def read(self) -> Trace | None:
        """The trace, read and then deleted from disk."""
        self.stop()
        if self._dir is None:
            return None
        try:
            return Trace.from_dir(self._dir, self.window_s)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def save(trace: Trace, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace.to_json(), f)


def load(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))
