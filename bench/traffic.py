"""The one traffic generator: per-client requests from a mix's data file and a seed.

A mix file (``bench/traffic/<mix>.json``) gives the loop, the length
distributions of prompts and outputs, and the prefill chunk::

    {"loop": "closed", "prefill_chunk": 16, "block": 64,
     "prompt": {"dist": "lognormal", "median": 52, "sigma": 0.8, "min": 16, "max": 256},
     "output": {"dist": "lognormal", "median": 162, "sigma": 0.8, "min": 1, "max": 767}}

Every block of ``block`` lengths holds the distribution's quantiles at the
block's midpoints, clipped to [min, max].  Each closed-loop client sends
its own endless stream: block after block, prompts and outputs each in an
order that depends on the client's rank alone.  The seed only deals the
ranks to the slots and draws the prompt tokens.  So every seed offers the
same clients, and so the same work at the same times, in another slot
order: a window of a run sees the same mix of lengths whatever the seed,
and every client sees the whole distribution in each of its blocks.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (plen,) int32
    max_new: int


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the midpoint quantiles of ``spec``'s distribution."""
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "lognormal":
        z = [NormalDist().inv_cdf(q) for q in qs]
        raw = [spec["median"] * math.exp(spec["sigma"] * x) for x in z]
    elif spec["dist"] == "fixed":
        raw = [spec["value"]] * n
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def validate(mix: dict, max_seq: int) -> None:
    """Raise if the closed-loop client could not serve a request of ``mix``."""
    if mix["loop"] != "closed":
        raise ValueError(f"unsupported loop {mix['loop']!r}")
    if mix["prompt"]["min"] < mix["prefill_chunk"]:
        raise ValueError("every prompt must be at least prefill_chunk tokens long")
    if mix["output"]["min"] < 1:
        raise ValueError("every request must ask for a token")
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    if not longest < max_seq:
        raise ValueError(f"prompt + output up to {longest} does not fit max_seq={max_seq}")


def clients(mix: dict, vocab: int, seed: int, n: int) -> list:
    """``n`` endless iterators of :class:`Request`, one per client slot."""
    block = int(mix["block"])
    prompts = quantile_lengths(mix["prompt"], block)
    outputs = quantile_lengths(mix["output"], block)
    ranks = np.random.default_rng(seed).permutation(n)
    return [_client(prompts, outputs, int(rank), slot, n, vocab, seed)
            for slot, rank in enumerate(ranks)]


def _client(prompts, outputs, rank: int, slot: int, n: int, vocab: int, seed: int):
    order = np.random.default_rng(rank)  # the lengths: the same under every seed
    tokens = np.random.default_rng([seed, slot])
    k = 0
    while True:
        for plen, out in zip(order.permutation(prompts), order.permutation(outputs),
                             strict=True):
            toks = tokens.integers(0, vocab, size=int(plen), dtype=np.int32)
            yield Request(k * n + slot, toks, int(out))
            k += 1
