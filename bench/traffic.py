"""The one general generator: every traffic mix is a JSON file of its
parameters under ``bench/traffic`` that these functions read.

Training (``"kind": "train_tokens"``): Zipf-distributed token rows, one
batch per step, after the program's synthetic data source, kept here so that
the yardstick does not change when the program does.  Batch ``i`` of seed
``s`` is a pure function of ``(s, i)``, drawn from a stream of its own: the
program's source starts step ``i + 1`` one Philox block after step ``i``,
so that its batches repeat each other shifted by a token or two.

Serving (``"kind": "requests"``): prompt and output lengths are lognormal,
clipped; arrivals are Poisson at a fixed rate, or a backlog due at once.
Lengths and gaps are stratified quantiles of their distributions, each in
a fixed order of its own (prompts, outputs and gaps not paired by rank).
Every seed gets the same sequence and draws only its own prompt tokens:
a window that serves the first requests of a backlog, or the arrivals of
its length, then serves the same work whatever the seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


# -- training ------------------------------------------------------------------


def train_batch(traffic: dict, vocab: int, seed: int, step: int) -> dict:
    """Tokens and next-token labels of one global batch."""
    rng = np.random.default_rng([seed, step, 0x7A1])
    b, s = traffic["batch"], traffic["seq"]
    raw = rng.zipf(traffic["zipf_a"], size=(b, s + 1)).astype(np.int64)
    toks = (raw % (vocab - 2)) + 1
    return {
        "tokens": toks[:, :s].astype(np.int32),
        "labels": toks[:, 1:].astype(np.int32),
    }


def train_batches(traffic: dict, vocab: int, seed: int, start: int = 0):
    step = start
    while True:
        yield train_batch(traffic, vocab, seed, step)
        step += 1


# -- serving -------------------------------------------------------------------


@dataclass(frozen=True)
class Req:
    rid: int
    due_s: float          # offset from the window's start
    prompt_len: int
    output_len: int


def _stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at stratified quantiles of a clipped lognormal,
    ascending."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf(u) for u in _stratified(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def request_count(traffic: dict, seconds: float) -> int:
    if traffic["arrivals"] == "backlog":
        return int(traffic["count"])
    # enough arrivals to cover the window with room to spare
    return int(math.ceil(traffic["rate_per_s"] * seconds * 1.25)) + 8


def fixed_order(values: np.ndarray, salt: int) -> np.ndarray:
    """``values`` in an order fixed by ``salt`` alone."""
    return values[np.random.default_rng([0x40D5, salt]).permutation(len(values))]


def requests(traffic: dict, seconds: float) -> list[Req]:
    """The requests of one run, sorted by due time."""
    n = request_count(traffic, seconds)
    prompts = fixed_order(lognormal_lengths(traffic["prompt"], n), 1)
    outputs = fixed_order(lognormal_lengths(traffic["output"], n), 2)
    if traffic["arrivals"] == "backlog":
        due = np.zeros(n)
    elif traffic["arrivals"] == "poisson":
        gaps = fixed_order(-np.log1p(-_stratified(n)) / traffic["rate_per_s"], 3)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    return [
        Req(i, float(due[i]), int(prompts[i]), int(outputs[i]))
        for i in range(n)
    ]


def prompt_tokens(seed: int, rid: int, length: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng([seed, rid, 0x7E4])
    return rng.integers(1, vocab - 1, size=length, dtype=np.int64).astype(np.int32)
