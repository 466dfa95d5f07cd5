"""Sweep an open-loop mix's arrival rate on the chip, to find the knee.

    python3 bench/tools/sweep.py --workload qwen2vl2b.chat --seed 1 \
        --rates 1,2,3,4 --seconds 30

One set-up, then one window per rate (the mix's other parameters
unchanged), each followed by a drain.  Per rate it prints the time to
first token (p50, p95), the p50 of the first and of the last quarter of
arrivals (a queue that grows shows as a later quarter that waits
longer), and how long past its arrivals the window ran.  The cell's
rate is set at about four fifths of the highest rate whose queue does
not grow (PERF.md).
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from common import build  # puts the checkout on sys.path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    s = build(args.workload, args.seed, args.seconds)
    for rate in [float(x) for x in args.rates.split(",")]:
        s.traffic.spec["rate_per_s"] = rate
        w = s.window(args.seconds)
        ttft = np.asarray(w.ttft_s) * 1e3
        q = max(1, len(ttft) // 4)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(ttft),
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "ttft_p95_ms": float(np.percentile(ttft, 95)),
            "first_quarter_p50_ms": float(np.median(ttft[:q])),
            "last_quarter_p50_ms": float(np.median(ttft[-q:])),
            "overrun_s": (w.t_end - w.t0) - args.seconds,
            "itl_p95_ms": float(np.percentile(w.gaps_s, 95) * 1e3),
            "tokens_per_s": w.tokens / (w.t_end - w.t0)}), flush=True)
        s.server.drain()
        s.live = []
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
