"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names its
configuration and its traffic mix; their files name the system adapter
and the plain reference (see ``bench/__init__.py``).  One process holds
the chip: it loads and warms up (``setup_s``), measures for
``--seconds``, reads the device's peak memory, frees the program's
state, checks what the window produced against the reference, and
prints one JSON line last on standard output:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown"], "checks"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of part
of the window.  A run that finds no TPU, or fewer chips than the cell
asks for, exits 2 and prints no result.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_BENCH = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_BENCH)
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, loadgen, peaks, traceread  # noqa: E402


@dataclasses.dataclass
class Run:
    """What a system adapter is given: the cell, its files, the seed."""
    manifest: harness.Manifest
    cell: dict
    config: dict                  # the configs entry, with "body"
    traffic: loadgen.Traffic
    seed: int
    seconds: float
    tracer: traceread.Tracer


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(manifest: harness.Manifest, workload: str, seed: int,
            seconds: float, trace: bool = False, *,
            require_tpu: bool = True, cache: bool = True):
    """The cell's system adapter, built but not set up, and the devices.
    Raises :class:`harness.NoChip` before any work when the chip is
    missing."""
    cell = manifest.cell(workload)
    config = manifest.config(cell["config"])
    devs = harness.devices(int(cell["chips"]), require_tpu)
    harness.CompileCounter.install()
    if cache:
        harness.enable_compile_cache()
    run = Run(manifest, cell, config,
              loadgen.Traffic(manifest.traffic(cell["traffic"]), seed),
              seed, seconds, traceread.Tracer(trace, seconds))
    return manifest.system(config["body"]["system"]).System(run), devs


def execute(args, manifest: harness.Manifest | None = None, *,
            require_tpu: bool = True,
            cache: bool = True) -> dict:
    """Run the cell; returns the result line (a dict)."""
    manifest = manifest or harness.Manifest()
    system, devs = prepare(manifest, args.workload, args.seed, args.seconds,
                           bool(args.trace), require_tpu=require_tpu,
                           cache=cache)
    counter = harness.CompileCounter
    system.setup()
    setup_s = time.perf_counter() - PROCESS_T0
    before = counter.count
    window = system.window(args.seconds)
    compiles = counter.count - before
    mem_peak = harness.memory_peak_bytes(devs)
    trace = system.run.tracer.read() if args.trace else None
    view = system.layer_view(window) if args.trace else None
    system.release()
    checks = system.check(window)

    for line in system.report(window):
        print(line, file=sys.stderr)
    print(f"compilations inside the window: {compiles}", file=sys.stderr)

    device = {**harness.device_record(devs),
              "memory_peak_bytes": mem_peak}
    e2e = {**system.end_to_end(window), "setup_s": (setup_s, "s")}
    result = {"correct": all(c["pass"] for c in checks.values()),
              "attempted": getattr(window, "requests", 0), "failed": 0}
    if args.trace:
        kind = devs[0].device_kind
        view.update(trace=trace, window=window,
                    peaks=lambda: peaks.peaks(kind))
        metrics = {}
        for m in manifest.per_layer(args.workload, set(e2e)):
            v = manifest.metric_reader(m["name"]).read(view)
            if v is not None:
                metrics[m["name"]] = harness.value(v, m["unit"])
        result["metrics"] = metrics
        device["busy_s"] = trace.busy_s() if trace else 0.0
        device["window_s"] = trace.window_s if trace else 0.0
        result["device"] = device
        if trace is not None:
            result["breakdown"] = trace.breakdown()
    else:
        result["metrics"] = {
            m["name"]: harness.value(e2e[m["name"]][0], m["unit"])
            for m in manifest.end_to_end(args.workload, set(e2e))}
        result["device"] = device
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    harness.print_checks(checks)
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = execute(args)
    except harness.NoChip as e:
        print(f"bench: {e}; not running", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
