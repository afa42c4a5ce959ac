"""Host spans and admission stamps of the serving path.

The scheduler and the executor name every phase of a step as a host span
(``PerfCounters.span``: a profiler annotation whose host seconds are also
summed per name).  A profiler trace of a tiny engine on the CPU must show
the spans nested as the step nests them, on the thread that called
``Engine.step``; the fake-plane tests check the admission stamp and the
two counters that split TTFT into queue wait and prefill.
"""

import glob

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import PerfCounters, VirtualMemory, VMemConfig
from repro.models import build_model
from repro.serve import (Engine, HostOnlyPlane, Request, Scheduler,
                         ServeConfig, ServeRequest)
from repro.serve.api import ServeResult

CALLER = "test.caller"


# ---------------------------------------------------------------------------
# the span primitive
# ---------------------------------------------------------------------------


def test_span_sums_host_seconds_per_name():
    c = PerfCounters()
    with c.span("outer", step=3):
        with c.span("inner", rows=2, bucket=0):
            pass
        with c.span("inner"):
            pass
    assert c.seconds("outer") >= c.seconds("inner") > 0.0
    assert set(c.report()["timers_s"]) == {"outer", "inner"}
    assert c.seconds("never") == 0.0


def test_span_records_its_time_when_the_body_raises():
    c = PerfCounters()
    with pytest.raises(ValueError):
        with c.span("failing"):
            raise ValueError("boom")
    assert c.seconds("failing") > 0.0


# ---------------------------------------------------------------------------
# spans in a profiler trace of a tiny engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_engine(tmp_path_factory):
    """Host events of a traced drive of a tiny engine: a dict from line
    to ``[(name, start_ns, end_ns)]`` for every line holding a ``serve.*``
    span, plus the engine's counters."""
    cfg = get_config("qwen2-7b", reduced=True)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, ServeConfig(
        page_size=4, num_pages=64, max_pages_per_seq=8, max_batch=4,
        max_horizon=2))

    def drive():
        rng = np.random.default_rng(0)
        for _ in range(2):
            eng.submit(ServeRequest(
                prompt=rng.integers(0, cfg.vocab_size, size=7).astype(
                    np.int32),
                max_new_tokens=4))
        while eng.scheduler.has_work:
            eng.step()

    try:
        drive()                               # compile outside the trace
        out = tmp_path_factory.mktemp("trace")
        with jax.profiler.trace(str(out)):
            with jax.profiler.TraceAnnotation(CALLER):
                drive()
        counters = eng.counters
    finally:
        eng.close()
    files = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if any(n.startswith("serve.") for n, _, _ in evs):
                lines[(plane.name, line.name)] = evs
    return lines, counters


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def test_spans_sit_on_the_callers_thread(traced_engine):
    lines, _ = traced_engine
    # one host line holds every serve.* span, and the caller's own
    # annotation: the spans never leave the thread that calls step()
    assert len(lines) == 1
    (evs,) = lines.values()
    names = {n for n, _, _ in evs}
    assert CALLER in names
    assert {"serve.sched.step", "serve.sched.restore", "serve.sched.admit",
            "serve.sched.plan", "serve.sched.commit", "serve.exec.prefill",
            "serve.exec.decode", "serve.exec.ptab_sync", "serve.exec.pack",
            "serve.exec.dispatch", "serve.exec.wait",
            "serve.exec.readback"} <= names
    caller = next(e for e in evs if e[0] == CALLER)
    assert all(_inside(e, caller) for e in evs if e[0].startswith("serve."))


def test_dispatch_nests_in_decode_nests_in_step(traced_engine):
    lines, _ = traced_engine
    (evs,) = lines.values()
    by = {}
    for e in evs:
        by.setdefault(e[0], []).append(e)
    steps, decodes = by["serve.sched.step"], by["serve.exec.decode"]
    # every decode call lies inside one step, and every dispatch inside
    # one data-plane call of the executor
    for d in decodes:
        assert any(_inside(d, s) for s in steps)
    calls = decodes + by["serve.exec.prefill"]
    for d in by["serve.exec.dispatch"] + by["serve.exec.wait"]:
        assert any(_inside(d, c) for c in calls)
    chained = [d for d in by["serve.exec.dispatch"]
               if any(_inside(d, c) for c in decodes)]
    assert len(chained) == len(decodes) > 0


def test_span_seconds_follow_the_trace(traced_engine):
    _, counters = traced_engine
    timers = counters.report()["timers_s"]
    for name in ("serve.sched.step", "serve.exec.decode",
                 "serve.exec.dispatch", "serve.exec.wait"):
        assert timers[name] > 0.0
    # a step holds its decode calls, and a call its dispatch and wait
    assert counters.seconds("serve.sched.step") >= counters.seconds(
        "serve.exec.decode")
    assert counters.seconds("serve.exec.decode") + counters.seconds(
        "serve.exec.prefill") >= counters.seconds(
        "serve.exec.dispatch") + counters.seconds("serve.exec.wait")


def test_scheduler_keeps_no_event_snapshots(traced_engine):
    _, counters = traced_engine
    assert counters.events() == []


# ---------------------------------------------------------------------------
# admission stamp and counters (fake plane)
# ---------------------------------------------------------------------------


def mk_sched(usable_pages=15, max_batch=3, max_pages=8, page_size=4):
    cfg = ServeConfig(page_size=page_size, num_pages=usable_pages + 1,
                      max_pages_per_seq=max_pages, max_batch=max_batch)
    vmem = VirtualMemory(VMemConfig(
        page_size=page_size, num_pages=usable_pages,
        max_pages_per_seq=max_pages, max_seqs=max_batch))
    sched = Scheduler(cfg, vmem)
    sched.attach_plane(HostOnlyPlane(vmem))
    return sched


def req(i, plen=6, max_new=4, **kw):
    return Request(req_id=i, prompt=np.arange(plen, dtype=np.int32),
                   max_new_tokens=max_new, **kw)


def _check_stamps(sched, reqs):
    c = sched.counters
    assert c.get("admitted") == len(reqs)
    assert c.get("queue_wait_us") == sum(
        round((r.t_admit - r.t_enqueue) * 1e6) for r in reqs)
    for r in reqs:
        assert 0.0 < r.t_enqueue <= r.t_admit <= r.t_first_token


def test_admission_stamps_and_queue_wait():
    sched = mk_sched()
    reqs = [req(i) for i in range(5)]
    for r in reqs:
        sched.submit(r)
    sched.step_plane()
    # three lanes: two requests are still queued, never admitted
    assert [r.t_admit > 0.0 for r in reqs] == [True] * 3 + [False] * 2
    assert sched.counters.get("admitted") == 3
    while sched.has_work:
        sched.step_plane()
    _check_stamps(sched, reqs)
    assert all(r.status == "done" for r in reqs)


def test_forked_and_radix_admissions_are_stamped():
    sched = mk_sched()
    sched.vmem.map_seq(sched.PREFIX_ID, 6)
    sched.prefix_len = 6
    forked = req(1, plen=3, share_prefix=True)
    sched.submit(forked)
    sched.step_plane()
    # a cold request whose first page is resident hits the radix cache
    owner, hit = req(2, plen=9, max_new=12), req(3, plen=9)
    sched.submit(owner)
    sched.step_plane()
    sched.submit(hit)
    while sched.has_work:
        sched.step_plane()
    assert sched.counters.get("forked_admissions") == 1
    assert sched.counters.get("prefix_hits") == 1
    _check_stamps(sched, [forked, owner, hit])


def test_restores_keep_the_first_admission():
    sched = mk_sched(usable_pages=4, max_batch=2)
    reqs = [req(i, plen=6, max_new=4) for i in range(2)]
    for r in reqs:
        sched.submit(r)
    sched.step_plane()
    stamps = [r.t_admit for r in reqs]
    assert sched.preempt_for(4)
    assert [r.status for r in reqs] == ["swapped"] * 2
    while sched.has_work:
        sched.step_plane()
    assert sched.counters.get("restores") >= 2
    assert [r.t_admit for r in reqs] == stamps
    _check_stamps(sched, reqs)


def test_failed_admission_is_not_counted():
    sched = mk_sched(max_pages=2)
    too_long = req(0, plen=12, max_new=4)     # past the page-table reach
    sched.submit(too_long)
    sched.step_plane()
    assert too_long.status == "failed"
    assert too_long.t_admit == 0.0
    assert sched.counters.get("admitted") == 0


def test_serve_result_carries_the_admission_stamp():
    sched = mk_sched()
    r = req(0)
    sched.submit(r)
    while sched.has_work:
        sched.step_plane()
    timing = ServeResult.from_request(r).timing
    assert timing.enqueue <= timing.admitted <= timing.first_token
    assert timing.admitted == r.t_admit

