"""The one traffic generator: reads a mix file (``bench/traffic/<mix>.json``)
and makes, from ``--seed``, the image pool and the arrival schedule.

A mix file holds only parameters::

    {"loop": "closed" | "open",
     "arrivals": {"process": "poisson", "rate_hz": ...}          (open)
               | {"process": "onoff", "on_rate_hz": ..., "mean_on_s": ...,
                  "mean_off_s": ..., "off_rate_hz": ...},
     "queued_batches": 2,                                         (closed)
     "pool_images": 4096, "sample": 1024,
     "server": {...}}                     (the engine; see program.py)

The arrival processes are copied from the program's
``repro.traffic.loadgen`` (``PoissonProcess``, ``OnOffProcess``), not
imported, so a change to the program cannot move the yardstick.
"""
from __future__ import annotations

import json
import os

import numpy as np

TRAFFIC_DIR = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as f:
        return json.load(f)


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent seeded streams (images, arrivals, sample) of one seed."""
    return np.random.default_rng([int(seed), stream])


def image_pool(seed: int, n: int, side: int, channels: int) -> np.ndarray:
    """``n`` float32 images in [0, 1): a random 4x4 colour field, scaled
    up, blended per image with pixel noise, so images differ in structure
    and in spread."""
    r = rng(seed, 0)
    coarse = r.random((n, 4, 4, channels), dtype=np.float32)
    up = np.repeat(np.repeat(coarse, side // 4, 1), side // 4, 2)
    noise = r.random((n, side, side, channels), dtype=np.float32)
    mix = r.random((n, 1, 1, 1), dtype=np.float32)
    return mix * up + (1 - mix) * noise


def poisson(r: np.random.Generator, horizon_s: float, rate_hz: float):
    """Constant-rate memoryless arrivals: exponential gaps."""
    if rate_hz <= 0:
        raise ValueError(f"rate_hz must be positive: {rate_hz}")
    out, t = [], 0.0
    while True:
        t += float(r.exponential(1.0 / rate_hz))
        if t >= horizon_s:
            return out
        out.append(t)


def onoff(r: np.random.Generator, horizon_s: float, on_rate_hz: float,
          mean_on_s: float, mean_off_s: float, off_rate_hz: float = 0.0):
    """Markov-modulated bursts: ON (``on_rate_hz``) for an exponential
    time of mean ``mean_on_s``, then OFF (``off_rate_hz``) for mean
    ``mean_off_s``, and again."""
    if on_rate_hz <= 0 or mean_on_s <= 0 or mean_off_s <= 0:
        raise ValueError("on_rate_hz, mean_on_s, mean_off_s must be > 0")
    out, t, on = [], 0.0, True
    state_end = float(r.exponential(mean_on_s))
    while True:
        rate = on_rate_hz if on else off_rate_hz
        t = state_end if rate <= 0 else t + float(r.exponential(1.0 / rate))
        while t >= state_end:             # the state ended before the arrival
            on = not on
            start = state_end
            state_end = start + float(r.exponential(
                mean_on_s if on else mean_off_s))
            rate = on_rate_hz if on else off_rate_hz
            t = state_end if rate <= 0 else \
                start + float(r.exponential(1.0 / rate))
        if t >= horizon_s:
            return out
        out.append(t)


PROCESSES = {"poisson": poisson, "onoff": onoff}


def arrivals(mix: dict, seed: int, horizon_s: float) -> np.ndarray:
    """Send times (s from the window's start) of an open-loop mix."""
    spec = dict(mix["arrivals"])
    process = PROCESSES[spec.pop("process")]
    return np.asarray(process(rng(seed, 1), horizon_s, **spec), np.float64)


def sample(seed: int, n: int, k: int) -> np.ndarray:
    """``k`` of ``n`` request indices (all if ``n <= k``), sorted."""
    if n <= k:
        return np.arange(n)
    return np.sort(rng(seed, 2).choice(n, size=k, replace=False))
