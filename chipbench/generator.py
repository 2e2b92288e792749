"""The one traffic generator: open-loop request schedules from a mix's
parameters (``traffic/<mix>.json``) and the seed.

Every run gets the same set of sizes and arrival gaps: lengths are the
distribution's quantiles at (i + 0.5) / n and the gaps the exponential
(Poisson) quantiles, each shuffled, and a fixed share of the requests
sample. The shuffle is drawn from the mix's ``schedule_seed`` where it
names one: then every run has one schedule, the same work arriving in the
same order, and the seed draws only the token ids and each request's
sampling seed; a result on such a mix holds for that one order. Without
it the run's seed draws the order too.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np
from statistics import NormalDist


@dataclasses.dataclass
class Req:
    due: float               # seconds after the window opens
    prompt: np.ndarray       # (n,) int32
    max_new: int
    temperature: float
    top_k: int
    top_p: float
    seed: int

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """n lengths at the distribution's (i + 0.5)/n quantiles, clipped."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def open_loop(mix: Dict, seconds: float, seed: int, vocab: int) -> List[Req]:
    """round(rate * seconds) requests, all due inside the window."""
    order = np.random.default_rng(mix.get("schedule_seed", seed))
    rate = mix["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-u) / rate)
    due = np.cumsum(gaps) * (seconds * n / (n + 1)) / gaps.sum()
    prompt = order.permutation(quantile_lengths(mix["prompt_len"], n))
    out = order.permutation(quantile_lengths(mix["output_len"], n))
    n_sampled = int(round(mix["sampled_share"] * n))
    sampled = np.zeros(n, bool)
    sampled[order.permutation(n)[:n_sampled]] = True
    rng = np.random.default_rng(seed)
    smp = mix["sampling"]
    reqs = []
    for i in range(n):
        reqs.append(Req(
            due=float(due[i]),
            prompt=rng.integers(0, vocab, int(prompt[i])).astype(np.int32),
            max_new=int(out[i]),
            temperature=smp["temperature"] if sampled[i] else 0.0,
            top_k=smp["top_k"] if sampled[i] else 0,
            top_p=smp["top_p"] if sampled[i] else 1.0,
            seed=int(rng.integers(0, 2 ** 31 - 1))))
    return reqs


def bucket(n: int, lo: int = 8) -> int:
    """Next power of two at or above n, from ``lo``."""
    b = lo
    while b < n:
        b *= 2
    return b


def step_shapes(mix: Dict, max_len: int, page_size: int):
    """Every (tokens per slot, page-table width) pair of serve-step calls
    that this mix can cause: prefill buckets of the prompt lengths (the
    remainder after a one-token prefix hit included) against every table
    width their pages and the other slots' contexts can reach, and decode
    (one token) at every width from the shortest context up."""
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    widths = [bucket(n, 1) for n in range(1, max_len // page_size + 1)]
    widths = sorted(set(widths))
    shapes = set()
    for s in sorted({min(bucket(n), max_len) for n in range(lo - 1, hi + 1)}):
        least = math.ceil(min(max(s // 2 + 1, lo - 1), hi) / page_size)
        shapes |= {(s, w) for w in widths if w >= bucket(least, 1)}
    least = math.ceil((lo + 1) / page_size)
    shapes |= {(1, w) for w in widths if w >= bucket(least, 1)}
    return sorted(shapes)
