"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines, and with ``--json OUT``
also writes machine-readable records (section / metric / value / unit /
wall_us / derived) for the CI benchmark-tracking gate
(``benchmarks.compare``) and the checked-in ``BENCH_*.json`` trajectory
points at the repo root.

Usage:
    PYTHONPATH=src python -m benchmarks.run [--fast] [--json out.json]
--fast skips the training-based figures (10/11), keeping the analytic
tables and the roofline report.
"""

import argparse
import json

from repro.launch.compile_cache import enable_compile_cache


def _sections(fast: bool) -> list:
    from benchmarks import (table1_macro, fig12_area_map,
                            fig14_system_energy, conv_kernel, placement,
                            roofline, scenario_swap, serve_load,
                            spec_decode, tuned_kernel)
    sections = [table1_macro, fig12_area_map, fig14_system_energy,
                placement, conv_kernel, tuned_kernel, serve_load,
                scenario_swap, spec_decode]
    if not fast:
        from benchmarks import fig10_generalization, fig11_du_sweep
        sections[1:1] = [fig10_generalization, fig11_du_sweep]
    sections.append(roofline)
    return sections


def parse_line(section: str, line: str) -> dict:
    """One ``name,us_per_call,derived`` CSV line -> a benchmark record."""
    name, us, derived = line.split(",", 2)
    return {"section": section, "metric": name, "value": float(us),
            "unit": "us_per_call", "wall_us": float(us), "derived": derived}


def main(argv=None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the training-based figures (10/11)")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write records as JSON (CI bench tracking)")
    args = ap.parse_args(argv)

    records = []
    print("name,us_per_call,derived")
    for mod in _sections(args.fast):
        section = mod.__name__.rsplit(".", 1)[-1]
        for line in mod.run():
            print(line, flush=True)
            if args.json:
                records.append(parse_line(section, line))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"# wrote {len(records)} records to {args.json}")


if __name__ == "__main__":
    main()
