"""The one load generator: a traffic mix file in, a list of requests out.

A mix (``traffic/<name>.json``) gives lengths as clipped lognormals and
arrivals as either ``"poisson"`` (open loop, on the wall clock) or
``"closed"`` (a queue kept at least ``queue_depth`` deep).  Every seed
gets the same multiset of lengths and of inter-arrival gaps, drawn as
stratified quantiles of the distributions, in an order of its own, with
prompt tokens of its own: runs with different seeds then do the same
amount of work, and the seed changes only which request has which shape
and when it comes.  With the mix's ``stratify_block`` k, the order is
balanced: every k consecutive values of each quantity hold one from each
of k strata of its range, so that every stretch of the run, the measured
window among them, gets about the same mix of long and short requests and
of long and short gaps, whatever the seed.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Item:
    """One request to send: ``due`` is seconds after the window opens
    (negative during the lead-in), ``None`` in a closed loop."""

    prompt: np.ndarray
    max_new: int
    due: float | None = None


def quantiles_lognormal(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """``n`` stratified quantiles of a lognormal, rounded and clipped to
    ``[lo, hi]``, in ascending order."""
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def quantiles_exponential(n: int, rate: float) -> np.ndarray:
    """``n`` stratified quantiles of an exponential of ``rate`` per second."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def shuffle(values: np.ndarray, block: int,
            rng: np.random.Generator) -> np.ndarray:
    """``values`` in an order drawn from ``rng``.  With ``block`` k > 1 the
    sorted values are dealt into groups of about k, one value from each of
    k strata (ranks ``g, g + G, g + 2G, ...`` for ``G`` groups); the groups
    follow one another in a drawn order, each shuffled within."""
    if block <= 1:
        return rng.permutation(values)
    ranked = np.sort(values)
    groups = math.ceil(len(ranked) / block)
    return np.concatenate([rng.permutation(ranked[g::groups])
                           for g in rng.permutation(groups)])


def _lengths(spec: dict, n: int, block: int,
             rng: np.random.Generator) -> np.ndarray:
    return shuffle(quantiles_lognormal(
        n, spec["median"], spec["sigma"], spec["min"], spec["max"]),
        block, rng)


def _items(mix: dict, n: int, vocab: int,
           rng: np.random.Generator) -> list[Item]:
    block = int(mix.get("stratify_block", 1))
    plens = _lengths(mix["prompt"], n, block, rng)
    outs = _lengths(mix["output"], n, block, rng)
    return [Item(rng.integers(0, vocab, size=int(p)).astype(np.int32), int(o))
            for p, o in zip(plens, outs)]


def open_loop(mix: dict, vocab: int, seconds: float, seed: int) -> list[Item]:
    """Poisson arrivals at ``mix["rate_per_s"]`` from ``-lead_in_s`` to
    the window's close, as ``Item`` s with their due times, in order."""
    rate, lead = float(mix["rate_per_s"]), float(mix["lead_in_s"])
    n = max(1, math.ceil(rate * (lead + seconds)))
    rng = np.random.default_rng(seed)
    gaps = shuffle(quantiles_exponential(n, rate),
                   int(mix.get("stratify_block", 1)), rng)
    items = _items(mix, n, vocab, rng)
    due = np.cumsum(gaps) - gaps[0] - lead
    for item, t in zip(items, due):
        item.due = float(t)
    return items


class ClosedLoop:
    """A stream of distinct requests from the seed, handed out so that the
    engine's queue stays at least ``queue_depth`` deep."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.depth = int(mix["queue_depth"])
        rng = np.random.default_rng(seed)
        self._items = _items(mix, int(mix["stream_len"]), vocab, rng)
        self._next = 0

    def top_up(self, queued: int) -> list[Item]:
        """The requests to submit now, given ``queued`` waiting ones."""
        need = max(0, self.depth - queued)
        if self._next + need > len(self._items):
            raise RuntimeError(
                f"closed loop ran out of its {len(self._items)} distinct "
                "requests; raise the mix's stream_len")
        out = self._items[self._next:self._next + need]
        self._next += need
        return out
