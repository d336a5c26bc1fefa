#!/usr/bin/env python3
"""Find the knee of an open-loop cell: run its mix at each of ``--rates``
(requests/s), one window each, in this one process, and print per rate the
latency percentiles, the completed rate and whether the backlog grew.

    python3 bench/sweep.py --workload resnet20-poisson --seed 5 \\
        --seconds 5 --rates 4000,8000,12000

The knee is the highest rate whose p95 stays within ``LIMIT_MS`` with no
backlog growing through the window;
a cell's mix then offers a fixed rate of about 0.8 x the knee.  The sweep
stops after two rates in a row fail.  The benchmark's own runs never
sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402

# p95 limit of the knee: the ``interactive`` class of the program's
# ``traffic/slo.py`` ``DEFAULT_CLASSES``
LIMIT_MS = 25.0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()

    import numpy as np

    cell = run.load_cell(args.workload)
    run.compile_cache()
    devices = run.accelerators(cell.chips)
    if devices is None:
        return 2
    windows, failed = {}, 0
    cell.end_to_end = [dict(name="fps", unit="images/s"),
                       dict(name="p50_ms", unit="ms"),
                       dict(name="p95_ms", unit="ms")]
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.mix = dict(cell.mix, arrivals=dict(process="poisson",
                                                rate_hz=rate))
        out = run.run_cell(cell, args.seed, args.seconds, False, devices,
                           keep_window=windows)
        win = windows["window"]
        lat = win.latency_s * 1e3
        fifth = max(len(lat) // 5, 1)
        first, last = (float(np.nanpercentile(lat[:fifth], 95)),
                       float(np.nanpercentile(lat[-fifth:], 95)))
        m = {k: v["value"] for k, v in out["metrics"].items()}
        ok = m["p95_ms"] <= LIMIT_MS and last <= 2 * first
        print(json.dumps(dict(
            rate_hz=rate, correct=out["correct"], p50_ms=m["p50_ms"],
            p95_ms=m["p95_ms"], completed_per_s=m["fps"],
            p95_first_fifth_ms=first, p95_last_fifth_ms=last,
            lateness_p99_ms=float(np.percentile(win.lateness_s, 99)) * 1e3,
            sustained=ok)), flush=True)
        failed = 0 if ok else failed + 1
        if failed == 2:          # past the knee: a growing backlog only
            break                # takes longer to drain at each rate
    return 0


if __name__ == "__main__":
    sys.exit(main())
