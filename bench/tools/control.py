"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/tools/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed: one set-up and one window at the cell's own size and
load, as a benchmark run; then, on the requests the check samples, the
program's reading (the number the check compares) and the control's:
the plain reference computed with an int4 trunk put in the program's
place, read by the same measure.  The limit lies between the largest
program reading and the smallest control reading (PERF.md).
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from common import build, free  # puts the checkout on sys.path


def cnn_readings(s, w) -> dict:
    ref = s.run.manifest.reference(s.body["reference"])
    prog, ctrl = [], []
    for idx, out in w.sample:
        frames = s.frames[idx]
        want = np.asarray(ref.forward(s.params, frames, s.body), np.float64)
        low = np.asarray(ref.forward(s.params, frames, s.body, 4), np.float64)
        norm = np.linalg.norm(want)
        prog.append(float(np.linalg.norm(np.asarray(out) - want) / norm))
        ctrl.append(float(np.linalg.norm(low - want) / norm))
    return {"program": max(prog), "control": min(ctrl),
            "control_max": max(ctrl), "requests": len(prog)}


def lm_readings(s, w) -> dict:
    prog, ctrl, tokens = [], [], 0
    for r in s.sample(w):
        prog.append(float(s.gaps(r).max()))
        ctrl.append(float(s.gaps(r, 4).max()))
        tokens += len(r.tokens)
    return {"program": max(prog), "control": min(ctrl),
            "control_max": max(ctrl), "requests": len(prog),
            "tokens": tokens}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    for seed in [int(x) for x in args.seeds.split(",")]:
        s = build(args.workload, seed, args.seconds)
        w = s.window(args.seconds)
        s.release()
        read = cnn_readings if hasattr(s, "frames") else lm_readings
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **read(s, w)}), flush=True)
        free(s)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
