"""Seeded request traffic in wall-clock seconds, read from a traffic file.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

- ``loop``: ``"open"`` (arrivals on a schedule, whatever the server does)
  or ``"closed"`` (``clients`` callers, each sending its next request when
  the last one finished);
- ``arrivals`` (open loop): ``{"process": "poisson" | "mmpp", "rate_rps",
  "burst_factor", "p_enter", "p_exit", "shape_seed"}``;
- ``prompt`` and ``output``: a length distribution each,
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}``;
- ``requests`` (closed loop): how many requests the pool of lengths holds.

The arrival times are fixed by the file (``shape_seed``) and so is the
multiset of lengths (stratified quantiles of each distribution), so every
seed offers the same work: the seed only deals the lengths to the arrivals
in another order and draws the prompt tokens.  Runs on different seeds
then differ by the order of the work and not by its amount.

The MMPP is the two-state process of the program's
``serving/sched/trace.py::bursty_trace`` (calm and burst states with
geometric dwell, the long-run rate held at ``rate_rps``), moved from
scheduler steps to seconds.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclass(frozen=True)
class Item:
    """One request: when it is due (seconds after the window opens; 0 in a
    closed loop), its prompt tokens and how many tokens it asks for."""

    at_s: float
    prompt: np.ndarray
    max_tokens: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose (``stream``) of one run seed; any whole
    number, however large, is a valid seed."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's stratified quantiles, in
    ascending order (the same multiset for every seed)."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(p)) for p in q])
        x = np.round(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
    elif spec["dist"] == "uniform":
        x = np.floor(lo + q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def arrivals(spec: dict, seconds: float) -> np.ndarray:
    """Arrival times in [0, seconds), fixed by the file's ``shape_seed``."""
    rng = np.random.default_rng(int(spec.get("shape_seed", 0)))
    rate = float(spec["rate_rps"])
    out: List[float] = []
    t = 0.0
    if spec["process"] == "poisson":
        while True:
            t += rng.exponential(1.0 / rate)
            if t >= seconds:
                break
            out.append(t)
    elif spec["process"] == "mmpp":
        bf = float(spec["burst_factor"])
        p_enter, p_exit = float(spec["p_enter"]), float(spec["p_exit"])
        frac_burst = p_enter / (p_enter + p_exit)
        calm_iat = (1.0 / rate) / (1.0 - frac_burst + frac_burst / bf)
        burst = False
        while True:
            t += rng.exponential(calm_iat / bf if burst else calm_iat)
            if t >= seconds:
                break
            out.append(t)
            burst = rng.random() >= p_exit if burst else rng.random() < p_enter
    else:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    return np.asarray(out)


def make_items(traffic: dict, seed: int, seconds: float,
               vocab: int) -> List[Item]:
    """The run's requests, in the order they are due."""
    if traffic["loop"] == "open":
        at = arrivals(traffic["arrivals"], seconds)
    else:
        at = np.zeros(int(traffic["requests"]))
    n = len(at)
    if n == 0:
        return []
    order = rng_for(seed, 1)
    plens = order.permutation(lengths(traffic["prompt"], n))
    olens = order.permutation(lengths(traffic["output"], n))
    tok = rng_for(seed, 2)
    return [Item(at_s=float(at[i]),
                 prompt=tok.integers(1, vocab, int(plens[i])).astype(np.int32),
                 max_tokens=int(olens[i]))
            for i in range(n)]


def longest(traffic: dict) -> int:
    """The most tokens one request can hold: longest prompt plus longest
    output (the last output token is never written to the cache)."""
    return int(traffic["prompt"]["max"]) + int(traffic["output"]["max"]) - 1

