"""One general generator for every traffic mix (`bench/mixes/*.json`).

A mix is data: an arrival process, length distributions, prompt buckets,
the batch, the KV cache, the request deadline.  The generator turns a mix
and a seed into a request list.  The seed changes the order of the work
and the prompt tokens, never the amount: every seed gets the same multiset
of prompt lengths, output lengths and inter-arrival gaps, drawn at evenly
spaced quantiles of each distribution.  Each multiset is dealt into
consecutive blocks of ``BLOCK`` arrivals so that every block holds one
value from each stratum of the sorted values (a short one, ..., a long
one), and the seed shuffles the order inside each block.  So two seeds
differ in which request comes when, not in how much there is to do, nor
in how much of it falls in any part of the window (which sets how many
tokens are still owed when the window closes).

The arrivals (``"kind": "exponential_blocks"``) are not a Poisson
process: every block of ``BLOCK`` gaps sums to about ``BLOCK`` mean gaps,
so bursts longer than a block never come, and long outputs are spread
evenly through the window.

The length parameterisation (a lognormal by its median and sigma, rounded
and clipped to [lo, hi]) follows ``runtime/loadgen.py``'s
``sample_lengths``; it is copied here so that a change to the program
cannot move the yardstick.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Request:
    rid: int
    due_s: float            # offset from the window's start (open loop)
    prompt_len: int         # after rounding up to a bucket
    output_len: int         # tokens the request is served, first included
    prompt: np.ndarray


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of one seed (seeds may exceed 32 bits)."""
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def quantile_lengths(n: int, dist: dict) -> list[int]:
    """``n`` lengths at the quantiles (i + 1/2) / n of ``dist``:
    ``{"kind": "lognormal", "median", "sigma", "lo", "hi"}`` or
    ``{"kind": "uniform", "lo", "hi"}`` (inclusive)."""
    us = [(i + 0.5) / n for i in range(n)]
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if dist["kind"] == "lognormal":
        mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
        vals = [math.exp(mu + sigma * _NORMAL.inv_cdf(u)) for u in us]
    elif dist["kind"] == "uniform":
        vals = [lo + u * (hi - lo + 1) - 0.5 for u in us]
    else:
        raise ValueError(f"unknown length distribution {dist['kind']!r}")
    return [int(min(max(round(v), lo), hi)) for v in vals]


def bucket(length: int, buckets: list[int]) -> int:
    """Round a prompt length up to the smallest bucket that holds it."""
    for b in sorted(buckets):
        if length <= b:
            return b
    raise ValueError(f"prompt of {length} tokens exceeds every bucket "
                     f"{sorted(buckets)}")


BLOCK = 6


def dealt(values, rng: np.random.Generator) -> list:
    """``values`` in blocks of ``BLOCK`` consecutive positions, each block
    one value from each stratum of the sorted values (dealt in serpentine
    order), shuffled within the block by ``rng``."""
    vals = sorted(values)
    m = -(-len(vals) // BLOCK)                      # number of blocks
    blocks: list[list] = [[] for _ in range(m)]
    for i, v in enumerate(vals):
        j = i % m
        blocks[j if (i // m) % 2 == 0 else m - 1 - j].append(v)
    out = []
    for b in blocks:
        rng.shuffle(b)
        out.extend(b)
    return out


def exponential_gaps(n: int, rate: float) -> list[float]:
    """``n`` inter-arrival gaps at ``rate`` per second, at evenly spaced
    quantiles of the exponential distribution."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def requests_for(mix: dict, seed: int, seconds: float,
                 vocab: int) -> list[Request]:
    """The requests of one run: ``round(rate * seconds)`` requests due
    inside the window (open loop), the first at its start, their gaps
    scaled so the last one falls inside it."""
    arrival = mix["arrival"]
    if arrival["kind"] != "exponential_blocks":
        raise ValueError(f"unknown arrival process {arrival['kind']!r}")
    rate = float(arrival["rate_rps"])
    n = max(1, round(rate * seconds))
    gaps = dealt(exponential_gaps(n, rate), rng_for(seed, 0))
    scale = seconds * n / (n + 0.5) / sum(gaps)
    dues = np.cumsum(np.asarray(gaps) * scale) - gaps[0] * scale
    prompts = dealt([bucket(p, mix["prompt_buckets"])
                     for p in quantile_lengths(n, mix["prompt"])],
                    rng_for(seed, 1))
    outputs = dealt(quantile_lengths(n, mix["output"]), rng_for(seed, 2))
    return [Request(rid=i, due_s=float(dues[i]), prompt_len=prompts[i],
                    output_len=outputs[i],
                    prompt=rng_for(seed, 3, i).integers(
                        0, vocab, size=prompts[i], dtype=np.int32))
            for i in range(n)]
