"""The one traffic generator: a traffic file's parameters plus a cell's
rate and slots in, requests and arrival times out.

An open-loop mix (``"arrivals": "poisson"``) serves one declared Poisson
sample: the traffic file's ``schedule_seed`` fixes the exponential gaps
(mean ``1 / rate``), the prompt lengths and the output lengths, each
from a stream of its own, request after request until the window ends.
The sample keeps the bunching a Poisson process has, and every run of a
cell serves the same arrivals and lengths, so a tail percentile follows
the program and not the seed.  Changing the rate stretches the same
sample in time (a knee sweep compares like with like).

A batch mix (``"arrivals": "batch"``) queues everything at t=0, with
enough requests to outlast the window at ``steps_per_s_bound`` decode
steps a second.  Its lengths are stratified: the draws sit at quantiles
``(i + 0.5) / n`` of the length distribution and the seed only permutes
them, so every seed offers the same work in another order.

In both, ``--seed`` draws the prompts' token ids.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()
# requests drawn for an open-loop sample; the window takes those due in it
OPEN_SAMPLE = 1024


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, u: np.ndarray) -> np.ndarray:
    """Integer lengths at quantiles ``u`` of the distribution ``spec``
    (``lognormal``: median, sigma; ``uniform``: integers min..max),
    clipped to [min, max]."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        out = np.rint(x)
    elif spec["dist"] == "uniform":
        out = lo + np.floor(u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(out, lo, hi).astype(np.int64)


def open_schedule(traffic: dict, *, seconds: float, rate_per_s: float
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(prompt lengths, output lengths, arrival offsets) of the declared
    Poisson sample's requests due inside ``seconds``."""
    if not rate_per_s:
        raise ValueError("an open-loop mix needs the cell's rate_per_s")
    gaps_rng, plen_rng, out_rng = (
        np.random.default_rng(s) for s in
        np.random.SeedSequence(traffic["schedule_seed"]).spawn(3))
    arrivals = np.cumsum(gaps_rng.standard_exponential(OPEN_SAMPLE)
                         / rate_per_s)
    n = int(np.searchsorted(arrivals, seconds))
    if n == OPEN_SAMPLE:
        raise ValueError(f"{OPEN_SAMPLE} requests do not fill {seconds} s "
                         f"at {rate_per_s} req/s")
    plens = lengths(traffic["prompt_len"], plen_rng.random(OPEN_SAMPLE))
    outs = lengths(traffic["output_len"], out_rng.random(OPEN_SAMPLE))
    return plens[:n], outs[:n], arrivals[:n]


def batch_count(traffic: dict, *, seconds: float, slots: int) -> int:
    per_slot = math.ceil(seconds * traffic["steps_per_s_bound"]
                         / traffic["output_len"]["min"])
    return slots * (1 + per_slot)


def generate(traffic: dict, *, seconds: float, slots: int,
             rate_per_s: float | None, seed: int, vocab: int
             ) -> tuple[list[np.ndarray], list[int], list[float] | None]:
    """(prompts, output lengths, arrival offsets or None for a batch)."""
    rng = np.random.default_rng(seed)
    if traffic["arrivals"] == "poisson":
        plens, outs, due = open_schedule(traffic, seconds=seconds,
                                         rate_per_s=rate_per_s)
        arrivals = [float(a) for a in due]
    elif traffic["arrivals"] == "batch":
        n = batch_count(traffic, seconds=seconds, slots=slots)
        plens = lengths(traffic["prompt_len"],
                        rng.permutation(_quantiles(n)))
        outs = lengths(traffic["output_len"], rng.permutation(_quantiles(n)))
        arrivals = None
    else:
        raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
    # ids 0 and 1 are left out: they are the usual pad and end ids
    prompts = [rng.integers(2, vocab, size=int(p)).astype(np.int32)
               for p in plens]
    return prompts, [int(o) for o in outs], arrivals
