"""The one traffic generator: a mix is a JSON file of parameters under
``traffic/``, and this module turns it and a seed into requests or batches.

Sizes and gaps are the quantiles of the mix's distributions at
``(i + 0.5) / n``, shuffled by the seed: every seed gets the same multiset
of prompt lengths, output lengths and arrival gaps, in another order, so
the seed changes which request comes when and never how much work a run
holds. An open loop's lead and its window each get a multiset of their
own, so that every seed also puts the same requests inside the window.
Token ids are drawn from the seed.

Kinds of mix:

- ``open_loop``: ``rate_per_s`` requests a second, Poisson gaps; each
  request is due at its arrival time. Arrivals start ``lead_s`` seconds
  before the window opens (due times below 0), so that set-up can bring the
  server to its steady load before the window measures it.
- ``offline``: a ``backlog`` of requests all due when the window opens.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float  # seconds after the window opens
    prompt: tuple
    max_new_tokens: int


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n stratified draws of a length distribution, in increasing order."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in q])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "loguniform":
        x = dist["min"] * (dist["max"] / dist["min"]) ** q
    elif kind == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"] + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def _part(mix: dict, seed: int, part: int, start: float, length: float, n: int):
    """n requests of stratified sizes arriving over ``length`` seconds from
    ``start``: the first at ``start``, Poisson gaps that fill the span."""
    prompts = _rng(seed, 10 * part + 1).permutation(quantiles(mix["prompt"], n))
    outputs = _rng(seed, 10 * part + 2).permutation(quantiles(mix["output"], n))
    if mix["kind"] == "open_loop":
        q = (np.arange(n) + 0.5) / n
        gaps = _rng(seed, 10 * part + 3).permutation(-np.log1p(-q))
        gaps *= length / gaps.sum()
        due = start + np.cumsum(gaps) - gaps
    elif mix["kind"] == "offline":
        due = np.full(n, start)
    else:
        raise ValueError(f"mix kind {mix['kind']!r} makes no requests")
    return due, prompts, outputs


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> list[Request]:
    """The requests of one run, in order of their due times."""
    if mix["kind"] == "open_loop":
        lead = mix.get("lead_s", 0.0)
        parts = [(-lead, lead, round(mix["rate_per_s"] * lead)),
                 (0.0, seconds, max(1, round(mix["rate_per_s"] * seconds)))]
    else:
        parts = [(0.0, seconds, mix["backlog"])]
    due, prompts, outputs = (np.concatenate(x) for x in zip(
        *(_part(mix, seed, i, *p) for i, p in enumerate(parts) if p[2])))
    tok = _rng(seed, 4)
    lo = mix.get("min_token_id", 0)
    return [Request(float(due[i]), tuple(int(t) for t in tok.integers(lo, vocab, int(prompts[i]))),
                    int(outputs[i])) for i in range(len(due))]
