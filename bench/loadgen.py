"""The one load generator: turns a traffic file and a seed into requests.

A traffic file (``bench/traffic/<mix>.json``) gives

* ``loop``: ``"open"``, independent users: requests are due on a Poisson
  schedule at ``rate_per_s`` whether or not earlier ones finished;
* ``prompt`` and ``output``: length distributions, ``{"dist": "lognormal",
  "median", "sigma", "min", "max"}`` or ``{"dist": "uniform", "min",
  "max"}``;
* ``strata``: how many requests in a row cover the whole of each
  distribution, and ``order_seed``: the draw that orders them (below).

Every seed does the same work.  The warm period and the measured window
are planned apart, each with ``round(rate x seconds)`` requests: their
prompt and output lengths are the distributions' quantiles at
``(i + 0.5) / n`` and their inter-arrival gaps the exponential's, scaled
to fill the span exactly.  They are ordered once, by ``order_seed``,
stratified: each quantity's sorted values fall into ``strata`` bands, and
every run of ``strata`` consecutive requests takes one value of each band,
so long prompts, long outputs and long gaps are spread over the span.  So
every run sends the same lengths at the same times: where requests arrive
in a queue decides its tails (the time to first token moved by a third
between orders of one set of lengths), and a run's seed is not to move
them.  The run's seed draws the prompt tokens.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""

    prompt: np.ndarray  # int32 token ids
    max_new: int
    due_s: float = 0.0  # seconds after traffic starts


def quantile_lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's quantiles (i + 0.5) / n."""
    p = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(q)) for q in p])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + p * (dist["max"] + 1 - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), dist["min"], dist["max"]).astype(np.int64)


def stratified(rng, values: np.ndarray, strata: int) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which every run of
    ``strata`` consecutive entries holds one value of each of ``strata``
    bands of the sorted values (the last run may be short)."""
    v = np.sort(values)
    # band b's values go to distinct runs; a band one short misses the last
    block = np.concatenate([rng.permutation(len(b)) for b in
                            np.array_split(np.arange(len(v)), strata)])
    return v[np.argsort(block + rng.random(len(v)), kind="stable")]


def _part(order, rng, traffic: Dict, vocab: int, n: int, span_s: float):
    """``n`` requests due in ``[0, span_s)``, ordered by ``order``, their
    tokens drawn from ``rng``."""
    k = int(traffic["strata"])
    p = (np.arange(n) + 0.5) / n
    gaps = stratified(order, -np.log1p(-p), k)
    gaps *= span_s / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    prompts = stratified(order, quantile_lengths(traffic["prompt"], n), k)
    outputs = stratified(order, quantile_lengths(traffic["output"], n), k)
    return [Planned(prompt=rng.integers(0, vocab, size=int(prompts[i]),
                                        dtype=np.int32),
                    max_new=int(outputs[i]), due_s=float(due[i]))
            for i in range(n)]


def plan(traffic: Dict, seed: int, vocab: int, warm_s: float,
         seconds: float, max_seq: int) -> List[Planned]:
    """The requests of one run: those due in the warm period's ``warm_s``
    seconds, then those due in the window's ``seconds``."""
    if traffic["loop"] != "open":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    need = traffic["prompt"]["max"] + traffic["output"]["max"]
    if need > max_seq:
        raise ValueError(f"traffic needs {need} positions, the cell's cache "
                         f"holds {max_seq}")
    order = np.random.default_rng(int(traffic["order_seed"]))
    rng = np.random.default_rng(int(seed))
    rate = float(traffic["rate_per_s"])
    out = []
    for t0, span in ((0.0, warm_s), (warm_s, seconds)):
        n = int(round(rate * span))
        if n < 1:
            continue
        for r in _part(order, rng, traffic, vocab, n, span):
            r.due_s += t0
            out.append(r)
    return out
