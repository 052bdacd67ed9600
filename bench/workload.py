"""Traffic generator: one general reader of the mixes in ``traffic/``.

A mix file gives lengths, arrivals and the deployment knobs; this module
turns it and a seed into requests.  Every seed gets the same multiset of
prompt lengths, output lengths and inter-arrival gaps -- stratified
quantiles of the stated distributions -- in a different order, with
different token ids.  So the amount of work in a window does not move
with the seed, and runs on different seeds spread no wider than runs on
one seed.

The length and gap laws follow ``repro.workload.synth`` (lognormal
lengths parameterised by their mean, exponential inter-arrival gaps),
copied here so the yardstick does not move with the program.  Arrival
kinds: ``exp_gaps``, an open loop whose gaps are the exponential law's
stratified quantiles -- in random order a Poisson process but for the
fixed multiset of gaps; with the mix's ``strata`` its bursts are milder
(see ``shuffle``) -- and ``backlog``, everything submitted at once.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

_STD_NORMAL = NormalDist()


@dataclass
class Item:
    """One request as the benchmark sees it: ``due`` is seconds after the
    window opens (``None``: submitted at once, as a backlog)."""
    index: int
    prompt: List[int]
    max_new_tokens: int
    due: Optional[float]
    in_window: bool


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent stream ``stream`` of a (possibly > 32-bit) seed."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def length_quantiles(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the stratified quantiles (i + 0.5) / n of the law
    in ``spec``: lognormal with the given mean and sigma, or uniform on
    [min, max]; clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        sigma = float(spec["sigma"])
        mu = math.log(float(spec["mean"])) - sigma ** 2 / 2
        z = np.array([_STD_NORMAL.inv_cdf(x) for x in u])
        vals = np.exp(mu + sigma * z)
    elif spec["dist"] == "uniform":
        vals = lo + u * (hi - lo + 1)
    else:
        raise ValueError(f"unknown length law {spec['dist']!r}")
    return np.clip(vals.astype(np.int64), lo, hi)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps of mean 1/rate at stratified
    quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def shuffle(values: np.ndarray, rng: np.random.Generator,
            strata: int = 0) -> np.ndarray:
    """``values`` (sorted) in an order drawn from ``rng``.  With
    ``strata``, the sorted values are cut into that many strata and every
    consecutive block of ``strata`` items takes one value of each stratum,
    so every stretch of the run carries the same spread of sizes."""
    if not strata:
        return rng.permutation(values)
    m = -(-len(values) // strata)
    cols = [rng.permutation(values[s * m:(s + 1) * m]) for s in range(strata)]
    out = []
    for j in range(m):
        out.extend(rng.permutation([c[j] for c in cols if j < len(c)]))
    return np.asarray(out)


def _draw(mix: Dict, n: int, rng: np.random.Generator, vocab: int,
          first_index: int, t_start: float, in_window: bool) -> List[Item]:
    k = int(mix.get("strata", 0))
    prompts = shuffle(length_quantiles(mix["prompt"], n), rng, k)
    outputs = shuffle(length_quantiles(mix["output"], n), rng, k)
    arrival = mix["arrival"]
    if arrival["kind"] == "exp_gaps":
        due = t_start + np.cumsum(
            shuffle(exp_gaps(float(arrival["rate"]), n), rng, k))
    elif arrival["kind"] == "backlog":
        due = [None] * n
    else:
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    items = []
    for i in range(n):
        toks = rng.integers(1, vocab, size=int(prompts[i])).tolist()
        items.append(Item(first_index + i, toks, int(outputs[i]),
                          None if due[i] is None else float(due[i]),
                          in_window))
    return items


def window_count(mix: Dict, seconds: float) -> int:
    """Requests the window holds: rate x seconds for an open loop, the
    mix's stated backlog otherwise."""
    arrival = mix["arrival"]
    if arrival["kind"] == "exp_gaps":
        return max(1, int(round(float(arrival["rate"]) * seconds)))
    return int(arrival["requests"])


def generate(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Item]:
    """The window's requests, then (open loop) a tail that keeps the same
    load on the system while the window's last requests finish; the tail
    is never measured."""
    rng = seed_rng(seed, 1)
    n = window_count(mix, seconds)
    items = _draw(mix, n, rng, vocab, 0, 0.0, True)
    if mix["arrival"]["kind"] == "exp_gaps":
        # stretch the stratified gaps so the window's last request is due
        # just inside the window, whatever their order
        last = items[-1].due
        for it in items:
            it.due *= seconds * (n - 0.5) / n / last
        tail_n = int(round(float(mix["arrival"]["rate"])
                           * float(mix.get("tail_seconds", 60))))
        items += _draw(mix, max(tail_n, 1), seed_rng(seed, 2), vocab, n,
                       items[-1].due, False)
    return items
