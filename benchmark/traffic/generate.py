"""The one general traffic generator.

A traffic mix is a DATA file (``traffic/<name>.json``): a ``kind`` and its
parameters — lengths, rates, sharing. ``generate`` reads it and returns the
seeded schedule; the same ``(file, seed)`` gives the same schedule, token
for token. A kind is a small module (``traffic/kinds/<kind>.py`` with
``generate(params, rng, vocab, seconds) -> dict``) built from the length
and arrival distributions below, so a later PR that wants a new mix of the
same kind adds one JSON file and no code.

Only numpy: schedules are made before anything touches a device.
"""
from __future__ import annotations

import importlib

import numpy as np


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` independent integer lengths from a distribution spec:
    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``,
    ``{"dist": "loguniform", "min": a, "max": b}`` or
    ``{"dist": "uniform", "min": a, "max": b}`` — clipped to [min, max]."""
    d = spec["dist"]
    lo, hi = int(spec["min"]), int(spec["max"])
    if d == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif d == "loguniform":
        x = np.exp(rng.uniform(np.log(lo), np.log(hi), n))
    elif d == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {d!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def draw_arrivals(rng: np.random.Generator, spec: dict,
                  seconds: float) -> np.ndarray:
    """Arrival offsets in [0, seconds) of ``{"process": "poisson", "rate":
    r}``: independent exponential gaps, so the count in a window varies
    with the seed as it does for independent users."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    rate = float(spec["rate"])
    t = np.cumsum(rng.exponential(1.0 / rate, int(rate * seconds * 1.5) + 16))
    return t[t < seconds]


def draw_tokens(rng: np.random.Generator, vocab: int, n: int) -> list[int]:
    return rng.integers(0, vocab, int(n)).tolist()


def generate(traffic: dict, seed: int, vocab: int, seconds: float) -> dict:
    """The schedule of one run. ``seconds`` is everything the run will
    offer load for (lead-in plus window)."""
    kind = importlib.import_module(f"benchmark.traffic.kinds.{traffic['kind']}")
    rng = np.random.default_rng(int(seed))
    out = kind.generate(traffic, rng, int(vocab), float(seconds))
    out["kind"] = traffic["kind"]
    return out
