"""The one generator that every traffic mix under ``bench/traffic`` feeds.

A mix is a JSON file of parameters.  Lengths and arrival gaps are
*stratified*: for n draws the values are the distribution's quantiles at
(i + 1/2)/n, rounded to the mix's step, and the seed only shuffles them.
So every seed offers the same work (the same multiset of prompt lengths,
output lengths and gaps, hence the same total) in another order, and
runs of different seeds differ by arrangement, not by amount.  Token ids
are uniform over the vocabulary, from the seed.

A length distribution is {"dist": "uniform"|"loguniform", "lo", "hi",
"step"} or {"fixed": n}.  Arrival gaps of an open loop are exponential
at ``rate_per_s`` (a Poisson process with stratified gaps); the seeded,
version-stable inverse transform of the program's ``serving/trace.py``
is the arithmetic, applied to quantiles instead of uniform draws.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    rid: str
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int
    due_s: float              # seconds after the window opens (open loop)


def quantile(spec: Dict, u: float) -> int:
    """The length at quantile ``u`` of a length distribution."""
    if "fixed" in spec:
        return int(spec["fixed"])
    lo, hi, step = spec["lo"], spec["hi"], spec.get("step", 1)
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    elif spec["dist"] == "loguniform":
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return int(min(hi, max(lo, step * math.floor(x / step + 0.5))))


def support(spec: Dict) -> List[int]:
    """Every length the distribution can give (the shapes to warm up)."""
    if "fixed" in spec:
        return [int(spec["fixed"])]
    step = spec.get("step", 1)
    first = step * math.ceil(spec["lo"] / step)
    return sorted({min(spec["hi"], max(spec["lo"], v))
                   for v in range(first, spec["hi"] + step, step)})


def stratified(spec: Dict, n: int, rng: np.random.Generator) -> List[int]:
    vals = [quantile(spec, (i + 0.5) / n) for i in range(n)]
    return [vals[i] for i in rng.permutation(n)]


def exp_gaps(rate: float, n: int, rng: np.random.Generator) -> List[float]:
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    return [gaps[i] for i in rng.permutation(n)]


def _block(mix: Dict, n: int, rng, start: float, vocab: int, first: int
           ) -> List[Request]:
    prompts = stratified(mix["prompt"], n, rng)
    outputs = stratified(mix["output"], n, rng)
    if mix["loop"] == "open":
        due = (start + np.cumsum(exp_gaps(mix["rate_per_s"], n, rng))).tolist()
    else:
        due = [0.0] * n
    return [Request(f"r{first + i:04d}",
                    rng.integers(0, vocab, p, dtype=np.int32), o, d)
            for i, (p, o, d) in enumerate(zip(prompts, outputs, due))]


def requests(mix: Dict, seed: int, vocab: int, seconds: float = 0.0
             ) -> List[Request]:
    """A serving mix's requests, in arrival order.  A closed loop has
    ``sessions``.  An open loop has round(rate x seconds) requests due
    inside the window (their stratified gaps sum to just under it), then
    as many again at the same rate for the drain after it; each block is
    stratified on its own, so every seed puts the same work in the
    window."""
    rng = np.random.default_rng(seed)
    if mix["loop"] != "open":
        return _block(mix, mix["sessions"], rng, 0.0, vocab, 0)
    n_w = max(1, round(mix["rate_per_s"] * seconds))
    n_d = max(1, math.ceil(mix["rate_per_s"] * mix["drain_s"]))
    window = _block(mix, n_w, rng, 0.0, vocab, 0)
    return window + _block(mix, n_d, rng, max(seconds, window[-1].due_s),
                           vocab, n_w)


def prompt_ids(seed: int, vocab: int, length: int) -> np.ndarray:
    """The one prompt of a batch-1 mix."""
    return np.random.default_rng(seed).integers(0, vocab, length,
                                                dtype=np.int32)
