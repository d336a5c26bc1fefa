#!/usr/bin/env python3
"""Split a traced run's device idle time by the program's own host spans.

    python3 bench/host_split.py --workload resnet20-offline --seed 7 \\
        --seconds 10

Runs ``bench/run.py``'s ``--trace 1`` run of the cell once, in this
process, with one difference: a live ``repro.obs`` session records the
program's host spans for the window (``spans.py``), and
``breakdown.idle_gaps`` charges each idle nanosecond to the innermost
harness or program span covering it.  The same run without the session is
``bench/run.py --trace 1``.

Prints the run's result line with one more key, ``host_split``: whether a
session was installed; ``host_ms_per_batch.offline`` or ``.poisson``
(``spans.py``) and the median ``engine.wait``; the window's summed
``engine.tick`` (or ``engine.dispatch`` + ``engine.harvest``) seconds
beside its summed ``bench.tick`` seconds; the share of idle time left on
``bench.tick`` itself; every innermost piece of 50 ms or more, with its
time into the window; and every garbage collection of 10 ms or more, with
its generation.  A program whose ``repro.obs`` lacks the profiler's time
base gets no session.

A stopgap until ``run.run_cell`` opens and closes the session itself (the
edits PERF.md lists under Open questions), when this module goes.  It
swaps the module attributes ``drive.closed_loop``, ``drive.open_loop``
and ``trace.reduce_file`` for the length of one run, so it works only
while ``run_cell`` calls them through those modules.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run, spans  # noqa: E402

TOP = 32                  # idle gap entries kept: every span name, and more


def traced_run(cell, seed: int, seconds: float, devices, **kw) -> dict:
    """``run.run_cell(..., trace=True)`` with the program's spans."""
    from bench import trace as tr
    from bench.traffic import drive

    got: dict = {"program": [], "session": False}
    loops = {"closed_loop": drive.closed_loop, "open_loop": drive.open_loop}
    reduce_file = tr.reduce_file

    def with_session(loop):
        def wrapped(*args, **kwargs):
            ob = spans.open_session()
            got["session"] = ob is not None
            try:
                return loop(*args, **kwargs)
            finally:
                got["program"] = spans.close_session(ob)
        return wrapped

    def reduce_nested(path, host, n_devices):
        from jax.profiler import ProfileData
        got["harness"] = list(host)
        return tr.reduce(ProfileData.from_file(path),
                         spans.nested(host, got["program"]), n_devices,
                         top=TOP)

    for name, loop in loops.items():
        setattr(drive, name, with_session(loop))
    tr.reduce_file = reduce_nested
    try:
        out = run.run_cell(cell, seed, seconds, True, devices, **kw)
    finally:
        for name, loop in loops.items():
            setattr(drive, name, loop)
        tr.reduce_file = reduce_file
    dev = out["device"]
    out["host_split"] = split(got["harness"], got["program"],
                              dict(out["breakdown"]["idle_gaps"]),
                              dev["window_s"] - dev["busy_s"],
                              got["session"])
    return out


def split(harness, program, idle_gaps: dict, idle_s: float,
          session: bool) -> dict:
    """The ``host_split`` entry of one run (see the module docstring)."""
    window = next(s for s in harness if s[0] == "bench.window")
    inside = spans.in_window(harness, program)
    pieces = spans.innermost(
        [x for x in harness if x[0] != "bench.window"] +
        [x[:3] for x in inside])
    return {
        "session": session,
        "host_ms_per_batch.offline": spans.host_ms_per_batch_offline(inside),
        "host_ms_per_batch.poisson": spans.host_ms_per_batch_poisson(inside),
        "engine_wait_ms": spans.median_ms(inside, "engine.wait"),
        "bench_tick_s": spans.total_s(harness, ("bench.tick",)),
        "engine_tick_s": spans.total_s(inside, ("engine.tick",)),
        "engine_dispatch_harvest_s": spans.total_s(
            inside, ("engine.dispatch", "engine.harvest")),
        "idle_on_bench_tick": (idle_gaps.get("bench.tick", 0.0) / idle_s
                               if idle_s > 0 else None),
        "stalls_over_50ms": spans.stalls(pieces, window[1]),
        "gc_over_10ms": [(gen, (s - window[1]) * 1e-9, (e - s) * 1e-6)
                         for name, s, e, gen in inside
                         if name == "gc" and e - s >= 10_000_000],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell = run.load_cell(args.workload)
    run.compile_cache()
    devices = run.accelerators(cell.chips)
    if devices is None:
        return 2
    out = traced_run(cell, args.seed, args.seconds, devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
