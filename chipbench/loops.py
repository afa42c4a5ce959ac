"""The two ways a window is driven: an open loop on the wall clock and a
closed loop that keeps the queue deep.  An arrival law
(``arrivals/<law>.py``) makes its requests and hands them to one of these.

Both take a ``feeder`` (``run.Feeder``: ``submit``, ``step``, ``queued``,
``logs``, ``engine``) and a ``tracer`` (``run.Tracer``) and return
``(t0, t1, t_end)`` on the ``time.perf_counter`` clock: the window's open
and close, and when the last step after it ended.
"""

from __future__ import annotations

import time
from collections import deque

import jax

import traffic


def drive_open(feeder, items: list[traffic.Item], seconds: float,
               tracer) -> tuple[float, float, float]:
    """Open loop: each request is submitted when due on the wall clock
    (window opens ``-items[0].due`` s after the first).  After the close,
    steps on without new arrivals until every request due in the window
    has its first token, for at most a minute."""
    lead = -items[0].due
    t_start = time.perf_counter()
    t0, t1 = t_start + lead, t_start + lead + seconds
    pending = deque(items)
    while True:
        now = time.perf_counter()
        tracer.poll(now - t0)
        if now >= t1:
            break
        while pending and t0 + pending[0].due <= now:
            item = pending.popleft()
            feeder.submit(item, t0 + item.due)
        if feeder.engine.scheduler.has_work:
            feeder.step()
        else:
            nxt = t0 + pending[0].due if pending else t1
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                time.sleep(max(0.0, min(nxt, t1) - time.perf_counter()))
    tracer.poll(float("inf"))
    deadline = t1 + 60.0
    while (any(r.due is not None and r.due < t1 and r.first is None
               for r in feeder.logs)
           and feeder.engine.scheduler.has_work
           and time.perf_counter() < deadline):
        feeder.step()
    return t0, t1, time.perf_counter()


def drive_closed(feeder, loop: traffic.ClosedLoop, seconds: float,
                 warm_steps: int, tracer) -> tuple[float, float, float]:
    """Closed loop: before every step the queue is topped up to the mix's
    depth.  ``warm_steps`` steps (the first admission wave and a few
    decode steps) run before the window opens."""
    def one():
        for item in loop.top_up(feeder.queued):
            feeder.submit(item, None)
        feeder.step()

    for _ in range(warm_steps):
        one()
    t0 = time.perf_counter()
    t1 = t0 + seconds
    while True:
        now = time.perf_counter()
        tracer.poll(now - t0)
        if now >= t1:
            break
        one()
    tracer.poll(float("inf"))
    return t0, t1, time.perf_counter()
