"""The one traffic generator: it reads a mix's parameters and nothing else.

Every seed gets the same multiset of sizes and of gaps between arrivals,
in another order.  Sizes are the stratified quantiles of the mix's
distribution, so the total work does not change with the seed and runs
with different seeds spread no more than two runs of one seed would.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np

from benchlib import seeding

_NORMAL = statistics.NormalDist()


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a length distribution, clipped and
    rounded up to the grid.  ``dist``: {"dist": "lognormal", "median",
    "sigma", "min", "max", "round_up_to"}."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    ps = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(p)) for p in ps])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    x = np.clip(x, dist["min"], dist["max"])
    grid = int(dist.get("round_up_to", 1))
    x = np.ceil(x / grid) * grid
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    uid: int
    due: float              # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, round(mix["arrivals"]["rate_per_s"] * seconds))


def serve_schedule(mix: dict, seconds: float, seed: int,
                   vocab: int) -> list[ServeRequest]:
    """Open-loop arrivals for one window of ``seconds``: exactly
    ``round(rate * seconds)`` requests, all due inside the window."""
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    n = n_requests(mix, seconds)
    rng = seeding.rng(seed, "traffic")
    prompts = rng.permutation(quantiles(mix["prompt"], n))
    outputs = rng.permutation(quantiles(mix["output"], n))
    # Exponential gaps as stratified quantiles, shuffled, scaled so the
    # last request is due half a mean gap before the window closes.
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps)
    due *= seconds * (n - 0.5) / n / due[-1]
    return [ServeRequest(uid=i, due=float(due[i]),
                         prompt=rng.integers(0, vocab, int(prompts[i]),
                                             dtype=np.int32),
                         max_new_tokens=int(outputs[i]))
            for i in range(n)]


def prompt_lengths(mix: dict, seconds: float) -> list[int]:
    """The distinct prompt lengths a window of ``seconds`` will send (the
    same for every seed)."""
    return sorted({int(x) for x in quantiles(mix["prompt"],
                                             n_requests(mix, seconds))})


def longest(mix: dict, seconds: float) -> tuple[int, int]:
    n = n_requests(mix, seconds)
    return (int(quantiles(mix["prompt"], n).max()),
            int(quantiles(mix["output"], n).max()))


def max_len(mix: dict) -> int:
    """Cache rows a slot needs for the longest request the mix can send."""
    return int(mix["prompt"]["max"]) + int(mix["output"]["max"])


def percentile(values, q: int) -> float:
    """The q-th percentile (1 <= q <= 99) by linear interpolation between
    order statistics (``statistics.quantiles``, inclusive method)."""
    values = list(values)
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=100,
                                      method="inclusive")[q - 1])
