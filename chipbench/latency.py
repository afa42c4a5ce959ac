"""Latency and rate arithmetic over per-request commit stamps.

Each request keeps the host-clock times at which its tokens were
committed (``commits``: ``(time, tokens)`` in order).  The window is
``[t0, t1)`` on the same clock.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class RequestLog:
    """What the benchmark saw of one request (``time.perf_counter`` s)."""

    prompt_len: int
    max_new: int
    due: float | None = None          # open loop: when it was due
    submitted: float = 0.0
    commits: list[tuple[float, int]] = dataclasses.field(default_factory=list)

    @property
    def first(self) -> float | None:
        return self.commits[0][0] if self.commits else None

    @property
    def served(self) -> int:
        return sum(n for _, n in self.commits)


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def ttfts(logs: list[RequestLog], t0: float, t1: float,
          t_end: float) -> tuple[list[float], int]:
    """``(TTFT seconds, no-first-token count)`` over every request due in
    the window: first commit minus due time; a request with no first
    token by ``t_end`` counts at ``t_end``."""
    out, missing = [], 0
    for r in logs:
        if r.due is None or not t0 <= r.due < t1:
            continue
        first = r.first
        if first is None:
            missing += 1
            first = t_end
        out.append(first - r.due)
    return out, missing


def tpots(logs: list[RequestLog], t0: float, t1: float) -> list[float]:
    """Per request with commits at two or more instants inside the window
    (begun before it or not): the mean time per token committed inside it
    after its first commit there, ``(last - first) / (tokens - first_n)``.
    The ``first_n`` tokens of the first commit (a whole fused horizon, for
    a request begun before the window) were produced before its stamp."""
    out = []
    for r in logs:
        inside = [(t, n) for t, n in r.commits if t0 <= t < t1]
        if len({t for t, _ in inside}) < 2:
            continue
        tokens = sum(n for _, n in inside)
        out.append((inside[-1][0] - inside[0][0]) / (tokens - inside[0][1]))
    return out


def tokens_in(logs: list[RequestLog], t0: float, t1: float) -> int:
    """Output tokens committed inside the window."""
    return sum(n for r in logs for t, n in r.commits if t0 <= t < t1)
