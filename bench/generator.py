"""The one generator of every traffic mix in ``bench/traffic/``.

A mix is a JSON file of parameters:

- ``arrival``: ``"poisson"``, open loop at ``rate_rps``, or ``"batch"``,
  an offline batch: every request arrives at t=0;
- ``rate_rps``: requests per second; a run of ``seconds`` sends
  ``round(rate_rps * seconds)`` requests (a batch cell's rate is the
  capacity it measures, so the batch lasts about ``seconds``);
- ``prompt`` and ``output``: token-length distributions,
  ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
  (clipped to [a, b]).

The lengths and inter-arrival gaps are stratified: the i-th of n values is
the distribution's (i + 1/2)/n quantile.  Which length goes with which
arrival is one fixed shuffle, the same for every seed; the seed draws the
prompt tokens (and the benchmark's weights).  So every run of a mix does
the same work on the same schedule, and queueing, which the order of long
and short requests moves by a factor of two in TTFT, does not change with
the seed.

A cell with a ``plant`` (``bench/weights.py``) draws its prompt tokens
from outside the marker ids and then sets, in each whole key block of the
prompt, a number of rows to marker tokens: the counts of
``plant["block_markers"]`` in a seeded order, one count to a block, so no
two blocks of a prompt hold the same number.  The last, partial block
holds none.

With ``plant["graded"]`` (``{"levels": [...], "rows": r}``) the marker id
``lo + g`` carries channel-0 level ``levels[g]`` (``bench/weights.py``)
and the plant is graded, for prompts longer than a ladder of counts
reaches: up to ``len(levels)`` whole blocks, drawn from the seed among all
but the first, each get ``r`` rows of one level, no two blocks the same
level; every other block holds no marker.  A block's largest row score
and its sum then order blocks alike, by level.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent
SCHEDULE_SEED = 0      # the one shuffle of lengths and gaps (not --seed)


@dataclass
class Spec:
    """One request as the generator makes it."""
    rid: int
    prompt: np.ndarray        # (L,) int32 token ids in [1, vocab)
    n_new: int
    arrival_s: float


def load(name: str, bench: Path = HERE) -> dict:
    """The traffic mix ``<bench>/traffic/<name>.json``."""
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def count(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_rps"] * seconds)))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """The n stratified lengths of a length distribution, ascending."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(np.int64)


def gaps(mix: dict, n: int) -> np.ndarray:
    """The n inter-arrival gaps (seconds), ascending."""
    if mix["arrival"] == "batch":
        return np.zeros(n)
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    return -np.log1p(-_quantiles(n)) / mix["rate_rps"]


def plant(tokens: np.ndarray, rng, plant: dict, block_k: int) -> None:
    """Set marker rows in every whole block of ``tokens`` (module
    docstring), in place."""
    lo, hi = plant["marker_ids"]
    n_full = len(tokens) // block_k
    if "graded" in plant:
        _graded(tokens, rng, lo, plant["graded"], n_full, block_k)
        return
    ladder = plant["block_markers"]
    if n_full > len(ladder):
        raise ValueError(f"a prompt of {len(tokens)} tokens has {n_full} "
                         f"whole blocks; the plant counts {len(ladder)}")
    counts = rng.permutation(ladder[:n_full])
    for b, m in enumerate(counts):
        rows = b * block_k + rng.choice(block_k, size=int(m), replace=False)
        tokens[rows] = rng.integers(lo, hi, size=int(m))


def _graded(tokens, rng, lo: int, graded: dict, n_full: int,
            block_k: int) -> None:
    """The graded plant (module docstring), in place."""
    n = min(len(graded["levels"]), n_full - 1)
    if n <= 0:
        return
    blocks = 1 + rng.choice(n_full - 1, size=n, replace=False)
    grades = rng.permutation(len(graded["levels"]))[:n]
    for b, g in zip(blocks, grades):
        rows = b * block_k + rng.choice(block_k, size=graded["rows"],
                                        replace=False)
        tokens[rows] = lo + g


def generate(mix: dict, seconds: float, seed: int, vocab: int,
             plant_spec: dict = None, block_k: int = 0) -> list:
    """The run's requests, ordered by arrival; with ``plant_spec``, prompts
    planted for blocks of ``block_k`` rows (module docstring)."""
    n = count(mix, seconds)
    order = np.random.default_rng(SCHEDULE_SEED)
    plen = order.permutation(lengths(mix["prompt"], n))
    nnew = order.permutation(lengths(mix["output"], n))
    arrival = np.cumsum(order.permutation(gaps(mix, n)))
    arrival -= arrival[0]              # the first request opens the window
    rng = np.random.default_rng(seed)
    low = plant_spec["marker_ids"][1] if plant_spec else 1
    out = []
    for i in range(n):
        tokens = rng.integers(low, vocab, size=int(plen[i])).astype(np.int32)
        if plant_spec:
            plant(tokens, rng, plant_spec, block_k)
        out.append(Spec(i, tokens, int(nnew[i]), float(arrival[i])))
    return out
