"""Discovery by name, the device check, the compile counter, the result.

A cell of ``BENCHMARK.json`` names its configuration and its traffic;
this module finds their files, loads the system adapter and the metric
readers they name, and assembles the result line the run prints last.
Nothing here knows a particular cell.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The run found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` as module ``name``."""
    if name in sys.modules:
        return sys.modules[name]
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """``BENCHMARK.json`` plus where the files it names are looked up.

    Configuration files are found by the path the manifest gives them,
    relative to the manifest's directory.  Traffic mixes, system
    adapters, references and metric readers are found by name under
    ``traffic/``, ``systems/``, ``configs/`` and ``metrics/`` of the
    first directory of ``search`` that has the file, then of ``bench/``:
    a test points ``search`` at a temporary directory to show that a new
    configuration, mix or metric needs only new files."""

    def __init__(self, path: str | None = None,
                 search: tuple[str, ...] = ()):
        self.path = path or os.path.join(ROOT, "BENCHMARK.json")
        self.root = os.path.dirname(os.path.abspath(self.path))
        self.search = tuple(search) + (BENCH_DIR,)
        self.data = load_json(self.path)

    def find(self, sub: str, filename: str) -> str:
        for d in self.search:
            path = os.path.join(d, sub, filename)
            if os.path.isfile(path):
                return path
        raise FileNotFoundError(f"no {sub}/{filename} under {self.search}")

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.data["workloads"]]
        raise KeyError(f"unknown workload {name!r}; known: {known}")

    def config(self, name: str) -> dict:
        """The configuration entry and its file's contents."""
        for c in self.data["configs"]:
            if c["name"] == name:
                return {**c, "body": load_json(os.path.join(self.root,
                                                            c["file"]))}
        raise KeyError(f"unknown configuration {name!r}")

    def traffic(self, name: str) -> dict:
        return load_json(self.find("traffic", f"{name}.json"))

    def system(self, name: str):
        return load_module(self.find("systems", f"{name}.py"),
                           f"_bench_system_{name}")

    def reference(self, file: str):
        """A configuration's plain reference, named in its file."""
        path = self.find("configs", file)
        stem = os.path.splitext(os.path.basename(file))[0]
        return load_module(path, f"_bench_reference_{stem}")

    def _applies(self, metric: dict, cell: str, reported: set) -> bool:
        if "workloads" in metric:
            return cell in metric["workloads"]
        # without the key: every cell that reports the metric it moves
        return metric.get("moves", metric["name"]) in reported

    def end_to_end(self, cell: str, system_metrics: set) -> list[dict]:
        """The end-to-end metrics this cell reports: those listing it,
        and those without a list that its system measures."""
        out = []
        for m in self.data["end_to_end"]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif m["name"] in system_metrics:
                out.append(m)
        return out

    def per_layer(self, cell: str, reported: set) -> list[dict]:
        return [m for m in self.data["per_layer"]
                if self._applies(m, cell, reported)]

    def metric_reader(self, name: str):
        return load_module(self.find("metrics", f"{name}.py"),
                           "_bench_metric_" + name.replace(".", "_"))


def devices(chips: int, require_tpu: bool = True) -> list:
    """The devices the cell runs on; raises :class:`NoChip` when JAX
    finds no TPU (``require_tpu``) or fewer devices than ``chips``."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found platform "
                     f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def device_record(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` at the root of
    the checkout: ``repro.launch.compile_cache``).

    Every program is kept, the quick ones too, and none is evicted, so
    that a second run of a cell loads everything it compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileCounter:
    """Counts executables JAX builds or loads from its cache.

    Each new program (a jitted function at a new shape, or an eager op
    at a new shape) records one backend-compile event; a count that
    moves inside the measured window means something compiled there."""

    _listening = False
    count = 0

    @classmethod
    def install(cls) -> type:
        if not cls._listening:
            import jax

            def on_event(event: str, duration: float, **_):
                if event == BACKEND_COMPILE_EVENT:
                    cls.count += 1

            jax.monitoring.register_event_duration_secs_listener(on_event)
            cls._listening = True
        return cls


def value(v: float, unit: str) -> dict:
    return {"value": float(v), "unit": unit}


def print_checks(checks: dict) -> None:
    """Each number compared, beside its limit: the last lines of
    standard error."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}, "
              f"{'pass' if c['pass'] else 'FAIL'})", file=sys.stderr)
