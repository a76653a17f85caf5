"""Seeded open-loop request generator for the serving cells.

A mix file (``traffic/<mix>.json``) gives the offered rate and the
distributions of prompt and output lengths. ``generate`` turns it into
the requests due in a window of ``seconds``:

- the number of requests is ``rate_per_s * seconds``, rounded;
- lengths are lognormal (``median``, ``sigma``) clipped to
  ``[min, max]``, and arrivals are Poisson (exponential gaps at the
  rate). Each is drawn as the stratified quantiles of its distribution,
  so every seed gets the same set of prompt lengths, output lengths and
  gaps; the seed decides their order, which prompt goes with which
  output length, and the prompt tokens. Runs with different seeds then
  offer the same work in a different order;
- prompt tokens are uniform over the vocabulary, so no two prompts share
  a prefix.

Arrivals are due at ``due_s`` after the window opens whether or not
earlier requests have finished (open loop).
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    due_s: float             # seconds after the window opens
    prompt: np.ndarray       # int32 token ids
    max_new: int


def quantiles(n: int, median: float, sigma: float, lo: int, hi: int
              ) -> np.ndarray:
    """n stratified draws of a lognormal clipped to [lo, hi], ascending."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def gaps(n: int, rate: float) -> np.ndarray:
    """n stratified exponential gaps at ``rate``, ascending."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def lengths(spec: dict, n: int) -> np.ndarray:
    return quantiles(n, spec["median"], spec["sigma"], spec["min"],
                     spec["max"])


def count(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def generate(mix: dict, seed: int, seconds: float, vocab: int
             ) -> List[Planned]:
    if mix["generator"] != "openloop":
        raise ValueError(f"mix is for generator {mix['generator']!r}")
    n = count(mix, seconds)
    rng = np.random.default_rng(seed)
    prompts = rng.permutation(lengths(mix["prompt_tokens"], n))
    outputs = rng.permutation(lengths(mix["output_tokens"], n))
    g = rng.permutation(gaps(n, mix["rate_per_s"]))
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    return [Planned(rid=i, due_s=float(due[i]),
                    prompt=rng.integers(0, vocab, int(prompts[i]),
                                        dtype=np.int32),
                    max_new=int(outputs[i]))
            for i in range(n)]
