"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) names its loop, its length
distributions with their quanta, and the scheduler that serves it:

    {"loop": "closed", "scheduler": "static", "max_seq": 1536,
     "prompt": {"median": 384, "sigma": 0.8, "min": 64, "max": 1024,
                "quantum": 128},
     "output": {"median": 192, "sigma": 0.7, "min": 32, "max": 512,
                "quantum": 32},
     "blocks": 16}

Lengths are lognormal (``median``, ``sigma``), rounded UP to a multiple of
``quantum`` and clipped to ``[min, max]``, so every length sits on a
quantum and the program meets a bounded set of shapes.  They are drawn
*stratified*: each block of ``block`` requests (the served batch) takes the
``block`` quantiles ``(i + 0.5) / block`` of the distribution, and the seed
only decides their order and the prompts' token ids.  So every seed asks
for the same work, in another order, and two seeds' runs differ by the
system's noise, not by the sizes they drew.

The only loop is ``closed``: every request is due at once.  The
generator is a copy of the ideas of ``data/datasets.py`` kept with the
benchmark, so a change to the program cannot change the traffic it is
measured on.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclass(frozen=True)
class Req:
    """One generated request: what the benchmark hands the program."""

    prompt: np.ndarray      # (n,) int32 token ids
    output_len: int         # tokens to generate, the first from prefill


def quantised(x: float, spec: Dict) -> int:
    """Round ``x`` up to the mix's quantum and clip it to [min, max]."""
    q = int(spec["quantum"])
    n = int(math.ceil(x / q)) * q
    return int(min(max(n, int(spec["min"])), int(spec["max"])))


def stratified_lengths(spec: Dict, n: int) -> List[int]:
    """The ``n`` quantiles ``(i + 0.5) / n`` of the lognormal ``spec``,
    quantised, in ascending order."""
    nd = NormalDist()
    mu, sigma = math.log(float(spec["median"])), float(spec["sigma"])
    return [quantised(math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)), spec)
            for i in range(n)]


def generate(mix: Dict, seed: int, vocab: int, block: int) -> List[Req]:
    """All requests of a run: ``mix["blocks"]`` blocks of ``block``
    requests each, deterministic in ``seed``."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    rng = np.random.default_rng([int(seed), 1])
    prompts = stratified_lengths(mix["prompt"], block)
    outputs = stratified_lengths(mix["output"], block)
    reqs: List[Req] = []
    for _ in range(int(mix["blocks"])):
        p_order = rng.permutation(block)
        o_order = rng.permutation(block)
        for i in range(block):
            reqs.append(Req(
                prompt=rng.integers(0, vocab, size=prompts[p_order[i]],
                                    dtype=np.int32),
                output_len=outputs[o_order[i]],
            ))
    longest = max(len(r.prompt) + r.output_len for r in reqs)
    if longest > int(mix["max_seq"]):
        raise ValueError(f"a request needs {longest} positions; max_seq is "
                         f"{mix['max_seq']}")
    return reqs
