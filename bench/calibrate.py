#!/usr/bin/env python3
"""Readings that the limits of ``check.py`` are set from, in one process.

    python3 bench/calibrate.py --workload resnet20-offline --seconds 2 \\
        --seeds 1,2,3 --control-seeds 4,5,6

For each of ``--seeds`` it runs the cell (a short window at the cell's own
load, the whole timed path) and prints the numbers the check compared.
For each of ``--control-seeds`` it puts the control in the program's
place: the plain reference at int4 (``reference.forward(bits=4)``), on as
many images of that seed's pool as a run compares, against the reference
at int8.  A sound program reads at or under each limit; the control has to
read over one.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def control_reading(cell, seed: int) -> dict:
    from bench import check, reference
    from bench.traffic import generate

    cfg, mix = cell.config, cell.mix
    net = reference.build_net(cfg)
    weights = reference.make_weights(net, cfg["weight_seed"])
    pool = generate.image_pool(seed, mix["pool_images"], cfg["img"],
                               cfg["in_channels"])
    served = [True] * mix["pool_images"]      # every pool image, once
    return check.check(net, weights, pool, served, seed, mix["sample"],
                       cfg["limits"], bits=4)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()

    cell = run.load_cell(args.workload)
    run.compile_cache()
    devices = run.accelerators(cell.chips)
    if devices is None:
        return 2
    for s in filter(None, args.seeds.split(",")):
        out = run.run_cell(cell, int(s), args.seconds, False, devices)
        print(json.dumps(dict(kind="program", seed=int(s),
                              correct=out["correct"],
                              checks=out["checks"])), flush=True)
    for s in filter(None, args.control_seeds.split(",")):
        checks = control_reading(cell, int(s))
        print(json.dumps(dict(kind="control", seed=int(s),
                              correct=all(v["value"] <= v["limit"]
                                          for v in checks.values()),
                              checks=checks)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
