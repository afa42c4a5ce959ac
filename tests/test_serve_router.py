"""Multi-replica router acceptance suite (marker: ``router``).

The contract under test: a :class:`ReplicaRouter` over N data planes is
*semantically invisible* — every request's token stream is identical to
the N=1 reference run, no request starves, global page/counter accounting
equals the sum of the per-replica accounting, and the merged ``done``
statuses are a permutation of the reference run's — for random workloads,
any N in {1, 2, 4}, and ANY deterministic fault schedule (growth-stall
page hogs, forced spills, injected restore failures, delayed
completions) running underneath.  The fake-plane tests here are pure
host policy (no device); :class:`TestRouterRealExecutors` repeats the
identity claim with real (optionally mesh-sharded) Executors and is
additionally marked ``sharded`` where it needs >1 XLA device.
"""

import collections
import copy

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                       # pragma: no cover
    from _prop_fallback import given, settings, st

from _fault_plane import (
    drive,
    drive_router,
    expected_output,
    make_replica,
)
from repro.serve import Replica, ReplicaRouter, ServeRequest, to_internal

pytestmark = pytest.mark.router


def req(i, plen=6, max_new=8, **kw):
    """Public-surface submission (Engine/Router take ONLY ServeRequest);
    scheduler-plane sites lower it explicitly via ``to_internal``."""
    return ServeRequest(req_id=i, prompt=np.arange(plen, dtype=np.int32),
                        max_new_tokens=max_new, **kw)


def make_router(n, policy="least_loaded", schedules=None, max_backlog=None,
                **kw):
    """N fault-plane replicas behind one router; returns (router, planes)."""
    replicas, planes = [], []
    for r in range(n):
        sched, plane = make_replica(
            replica_id=r, schedule=(schedules or {}).get(r, ()), **kw
        )
        replicas.append(Replica(replica_id=r, scheduler=sched, plane=plane))
        planes.append(plane)
    return ReplicaRouter(replicas, policy=policy,
                         max_backlog=max_backlog), planes


def outputs(done):
    return {rid: [int(x) for x in r.output] for rid, r in done.items()}


def statuses(done):
    return sorted((rid, r.status) for rid, r in done.items())


def preload_fake_prefix(replica, plen):
    """Resident shared prefix on a fake replica: host bookkeeping only."""
    s = replica.scheduler
    s.vmem.map_seq(s.PREFIX_ID, plen)
    s.prefix_len = plen


# ---------------------------------------------------------------------------
# randomized workload / fault-schedule generators (reachable by design:
# every request's unshared lifetime footprint fits one replica's pool, so
# forced spills can delay but never legitimately fail a request — which is
# what makes "statuses are a permutation of the reference" a theorem)
# ---------------------------------------------------------------------------

USABLE_PAGES = 8


def gen_workload(rng):
    n = int(rng.integers(2, 9))
    return [req(i, plen=int(rng.integers(1, 13)),
                max_new=int(rng.integers(1, 11))) for i in range(n)]


def gen_faults(rng, reqs, steps_hi=30):
    events = []
    rids = [r.req_id for r in reqs]
    for _ in range(int(rng.integers(0, 5))):
        kind = ["hog", "force_spill", "fail_restore", "delay_done"][
            int(rng.integers(0, 4))
        ]
        step = int(rng.integers(1, steps_hi))
        rid = int(rng.choice(rids))
        if kind == "hog":
            events.append(("hog", step, int(rng.integers(1, 4)),
                           int(rng.integers(1, 7))))
        elif kind == "force_spill":
            events.append(("force_spill", step, rid))
        elif kind == "fail_restore":
            events.append(("fail_restore", step, rid,
                           int(rng.integers(1, 4))))
        else:
            events.append(("delay_done", step, rid,
                           int(rng.integers(1, 4))))
    return tuple(events)


# ---------------------------------------------------------------------------
# the headline property: fault-injected replica sweep vs N=1 reference
# ---------------------------------------------------------------------------


class TestFaultInjectedReplicaSweep:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(min_value=0, max_value=2**32 - 1))
    def test_token_identity_no_starvation_and_accounting(self, seed):
        rng = np.random.default_rng(seed)
        reqs = gen_workload(rng)

        # fault-free N=1 reference run
        ref, ref_planes = make_router(1, usable_pages=USABLE_PAGES)
        for r in reqs:
            ref.submit(copy.deepcopy(r))
        assert drive_router(ref, ref_planes) < 500
        ref_done = {rid: r for rid, r in ref.done.items()}
        ref_out = outputs(ref_done)
        # the closed form: the reference itself must deliver the analytic
        # per-request stream in full
        assert ref_out == {r.req_id: expected_output(r) for r in reqs}
        assert all(r.status == "done" for r in ref_done.values())

        for n in (1, 2, 4):
            schedules = {i: gen_faults(rng, reqs) for i in range(n)}
            router, planes = make_router(n, schedules=schedules,
                                         usable_pages=USABLE_PAGES)
            for r in reqs:
                router.submit(copy.deepcopy(r))
            steps = drive_router(router, planes)
            assert steps < 500, f"N={n}: starvation (drive never drained)"
            done = router.done
            # token identity with the N=1 reference, request by request
            assert outputs(done) == ref_out, f"N={n} diverged"
            # done statuses are a permutation of the reference run's
            assert statuses(done) == statuses(ref_done)
            # cross-replica conservation: pages, requests, placements
            router.check_invariants()
            # global accounting equals the sum of replica accounting,
            # recomputed by hand (not via the router's own helper)
            manual = collections.Counter()
            for rep in router.replicas:
                manual.update(rep.scheduler.counters.counters)
            manual.update(router.counters.counters)
            assert router.global_counters() == manual
            pages = collections.Counter()
            for rep in router.replicas:
                pages.update(rep.page_report())
            assert router.global_page_report() == dict(pages)
            # exactly one decode token per request-step actually decoded
            total = router.global_counters()
            assert total["decode_tokens"] == sum(
                max(2, r.max_new_tokens) - 1 for r in reqs
            )
            assert total["completed"] == len(reqs)
            assert total["placements"] == len(reqs)


# ---------------------------------------------------------------------------
# counter invariants (satellite): monotone counters, totals = sum of parts
# ---------------------------------------------------------------------------


WATCHED = ("host_syncs", "ptab_syncs", "ptab_rows_uploaded",
           "decode_horizon", "decode_tokens", "decode_dispatches",
           "preemptions", "restores", "restore_failures", "page_faults",
           "submitted", "completed")


class TestCounterInvariants:
    def test_counters_monotone_across_fault_sequence(self):
        """Every accounting counter is monotone non-decreasing through a
        preempt -> restore-failure -> hog -> restore sequence."""
        sched, plane = make_replica(
            usable_pages=6, max_batch=2,
            schedule=(("force_spill", 4, 0), ("fail_restore", 5, 0, 2),
                      ("hog", 8, 2, 3)),
        )
        for i in range(4):
            sched.submit(to_internal(req(i, plen=6, max_new=8)))
        last = {k: 0 for k in WATCHED}
        steps = 0
        while sched.has_work and steps < 300:
            steps += 1
            plane.tick(steps)
            sched.step_plane()
            for k in WATCHED:
                v = sched.counters.get(k)
                assert v >= last[k], f"{k} went backwards at step {steps}"
                last[k] = v
        assert steps < 300 and not sched.has_work
        assert last["restore_failures"] == 2     # both injected denials
        assert last["preemptions"] >= 1
        assert last["restores"] >= 1
        assert last["completed"] == 4
        sched.vmem.check_invariants()

    def test_totals_equal_replica_sums_across_preempt_fork_restore(self):
        """N=2 with shared prefixes, tight pools and forced spills: every
        merged counter equals the sum of the per-replica values, and the
        preempt/fork/restore machinery all actually fired."""
        router, planes = make_router(
            2, usable_pages=6, max_batch=2,
            schedules={0: (("force_spill", 6, 0),),
                       1: (("force_spill", 7, 1),)},
        )
        for rep in router.replicas:
            preload_fake_prefix(rep, plen=6)
        reqs = [req(i, plen=4, max_new=8, share_prefix=(i % 2 == 0))
                for i in range(6)]
        for r in reqs:
            router.submit(copy.deepcopy(r))
        assert drive_router(router, planes) < 500
        total = router.global_counters()
        for name in set(total) | set(WATCHED):
            parts = sum(rep.scheduler.counters.get(name)
                        for rep in router.replicas)
            parts += router.counters.get(name)
            assert total[name] == parts, name
        assert total["forked_admissions"] > 0
        assert total["preemptions"] >= 2
        assert total["restores"] >= 1
        # both replicas really decoded (per-replica counters all live)
        for rep in router.replicas:
            assert rep.scheduler.counters.get("host_syncs") > 0
            assert rep.scheduler.counters.get("decode_tokens") > 0
        assert outputs(router.done) == {
            r.req_id: expected_output(r) for r in reqs
        }
        router.check_invariants()


# ---------------------------------------------------------------------------
# placement policies
# ---------------------------------------------------------------------------


class TestPlacement:
    def test_round_robin_cycles_over_replicas(self):
        router, planes = make_router(3, policy="round_robin")
        for i in range(6):
            router.submit(req(i))
        order = [p.payload[1] for p in router.counters.events("place")]
        assert order == [0, 1, 2, 0, 1, 2]
        assert drive_router(router, planes) < 500
        for i in range(3):
            assert router.counters.get(f"placements_replica{i}") == 2
        router.check_invariants()

    def test_least_loaded_spreads_a_burst(self):
        """Backlogged page demand counts as load, so a burst submitted
        before any step runs alternates instead of piling on replica 0."""
        router, planes = make_router(2, policy="least_loaded")
        for i in range(4):
            router.submit(req(i, plen=6))
        order = [p.payload[1] for p in router.counters.events("place")]
        assert order == [0, 1, 0, 1]
        assert drive_router(router, planes) < 500
        router.check_invariants()

    def test_fork_affinity_pins_to_prefix_replica_and_counts_declines(self):
        """COW forks land on the (more loaded) prefix-holding replica —
        prefix sharing beats load balance — and each overridden base-
        policy choice is counted as a declined migration."""
        router, planes = make_router(2)
        preload_fake_prefix(router.replicas[1], plen=6)   # 2 pages pinned
        router.submit(req(0, plen=4, share_prefix=True))
        router.submit(req(1, plen=4, share_prefix=True))
        order = [p.payload[1] for p in router.counters.events("place")]
        assert order == [1, 1]                 # affinity, not least-loaded
        assert router.counters.get("migrations_declined") == 2
        router.submit(req(2, plen=4))          # plain: load balance rules
        assert router.counters.events("place")[-1].payload[1] == 0
        assert drive_router(router, planes) < 500
        done = router.done
        assert statuses(done) == [(0, "done"), (1, "done"), (2, "done")]
        assert outputs(done)[0] == expected_output(req(0, 4, 8))
        router.check_invariants()

    def test_backlog_diverted_fork_is_not_a_declined_migration(self):
        """``migrations_declined`` counts only AFFINITY overrides: when a
        backlog bound (not fork affinity) diverts the placement away from
        the unconstrained best replica, the counter must not move —
        the baseline choice is ranked under the same backlog filter."""
        router, planes = make_router(3, max_backlog=1)
        preload_fake_prefix(router.replicas[0], plen=6)
        preload_fake_prefix(router.replicas[1], plen=6)
        # replica 0: prefix (2 pages) + a queued request -> at backlog AND
        # still the overall least-loaded is replica 2 (no prefix, empty)
        router.replicas[0].scheduler.submit(to_internal(req(90, plen=2)))
        router.submit(req(0, plen=4, share_prefix=True))
        # eligible = {0, 1}; 0 is backlog-full -> choice = 1.  The
        # affinity-free baseline under the same backlog filter is
        # replica 2 (empty), so this IS a declined migration...
        assert router.counters.events("place")[-1].payload[1] == 1
        assert router.counters.get("migrations_declined") == 1
        # ...but when affinity and the filtered baseline agree, it is not:
        # replica 1 now carries the fork, replica 0 is still backlog-full,
        # and replica 2 stays the baseline — a second fork landing on 1
        # again declines again, while a PLAIN request diverted by nothing
        # counts nothing.
        before = router.counters.get("migrations_declined")
        router.submit(req(1, plen=4))                  # plain -> replica 2
        assert router.counters.events("place")[-1].payload[1] == 2
        assert router.counters.get("migrations_declined") == before

    def test_share_prefix_without_any_prefix_replica_raises(self):
        router, _ = make_router(2)
        with pytest.raises(ValueError, match="share_prefix"):
            router.submit(req(0, share_prefix=True))

    def test_bounded_backlog_defers_and_counts_queue_waits(self):
        router, planes = make_router(2, max_backlog=1, max_batch=1,
                                     usable_pages=4)
        reqs = [req(i, plen=4, max_new=6) for i in range(5)]
        for r in reqs:
            router.submit(copy.deepcopy(r))
        # two placed immediately (one backlog slot per replica), the rest
        # wait in the global admission queue
        assert router.counters.get("placements") == 2
        assert len(router.queue) == 3
        assert drive_router(router, planes) < 500
        assert router.counters.get("cross_replica_queue_waits") > 0
        assert router.counters.get("placements") == 5
        assert outputs(router.done) == {
            r.req_id: expected_output(r) for r in reqs
        }
        router.check_invariants()

    def test_rejects_bad_configurations(self):
        with pytest.raises(ValueError, match="at least one"):
            ReplicaRouter([])
        sched, plane = make_replica()
        rep = Replica(replica_id=0, scheduler=sched, plane=plane)
        with pytest.raises(ValueError, match="duplicate"):
            ReplicaRouter([rep, rep])
        with pytest.raises(ValueError, match="policy"):
            ReplicaRouter([rep], policy="hottest_replica")


# ---------------------------------------------------------------------------
# N=1 equivalence: the router is exactly the single-replica engine loop
# ---------------------------------------------------------------------------


class TestN1Equivalence:
    def test_n1_router_is_callwise_identical_to_bare_scheduler_loop(self):
        reqs = [req(i, plen=5 + i, max_new=6) for i in range(4)]
        sched, plane = make_replica(usable_pages=8, max_batch=2)
        for r in reqs:
            sched.submit(to_internal(copy.deepcopy(r)))
        drive(sched, plane)
        router, planes = make_router(1, usable_pages=8, max_batch=2)
        for r in reqs:
            router.submit(copy.deepcopy(r))
        drive_router(router, planes)
        rsched = router.replicas[0].scheduler
        assert outputs(sched.done) == outputs(router.done)
        assert list(sched.done) == list(router.done)   # completion ORDER
        assert sched.step_i == rsched.step_i
        # identical per-replica counters modulo the router's own placement
        # bookkeeping and the one wall-clock sum (queue wait, in us)
        a = dict(sched.counters.counters)
        b = dict(rsched.counters.counters)
        b.pop("router_placements")
        assert a.pop("queue_wait_us") >= 0 and b.pop("queue_wait_us") >= 0
        assert a == b
        # the fake planes saw the identical call sequence
        assert plane.events == planes[0].events


# ---------------------------------------------------------------------------
# run-budget boundary (satellite): retire exactly on the last tick
# ---------------------------------------------------------------------------


class TestRouterRealEngines:
    """The identity claim with REAL device executors: N single-device
    Engines behind the router reproduce the plain-engine token stream
    (greedy decoding is per-sequence, so batching/placement must be
    invisible).  Roomy pools keep every replica off the degraded
    growth-stall path, whose scratch-routed writes are the one
    *intentional* token-stream divergence in the engine."""

    @pytest.fixture(scope="class")
    def real_setup(self):
        import jax

        from repro.configs import get_config
        from repro.models import build_model
        from repro.serve import ServeConfig
        cfg = get_config("qwen2-7b", reduced=True)
        model = build_model(cfg, remat=False)
        params = model.init(jax.random.PRNGKey(0))
        scfg = ServeConfig(page_size=4, num_pages=64, max_pages_per_seq=32,
                           max_batch=3)
        return cfg, model, params, scfg

    @staticmethod
    def _workload(cfg, n, seed, max_new=8):
        rng = np.random.default_rng(seed)
        return [ServeRequest(req_id=i,
                             prompt=rng.integers(0, cfg.vocab_size,
                                                 size=int(rng.integers(5, 12))
                                                 ).astype(np.int32),
                             max_new_tokens=max_new) for i in range(n)]

    def _reference(self, real_setup, reqs):
        from repro.serve import Engine
        cfg, model, params, scfg = real_setup
        ref = Engine(model, params, scfg)
        for r in reqs:
            ref.submit(copy.deepcopy(r))
        return ref.run()

    def _router_over(self, real_setup, n, mesh=None):
        from repro.serve import Engine
        cfg, model, params, scfg = real_setup
        engines = [Engine(model, params, scfg, mesh=mesh) for _ in range(n)]
        router = ReplicaRouter(
            [eng.as_replica(i) for i, eng in enumerate(engines)]
        )
        return router, engines

    def test_n2_token_identity_vs_single_engine(self, real_setup):
        cfg = real_setup[0]
        reqs = self._workload(cfg, n=5, seed=3)
        ref_done = self._reference(real_setup, reqs)
        router, engines = self._router_over(real_setup, n=2)
        for r in reqs:
            router.submit(copy.deepcopy(r))
        done = router.run()
        assert outputs(done) == outputs(ref_done)
        assert statuses(done) == statuses(ref_done)
        # the fleet really load-balanced (both data planes decoded)
        for i in range(2):
            assert router.counters.get(f"placements_replica{i}") > 0
        for eng in engines:
            assert eng.counters.get("decode_tokens") > 0
        router.check_invariants()


@pytest.mark.sharded
class TestRouterRealShardedExecutors(TestRouterRealEngines):
    """ISSUE acceptance: N=2 REAL executors, each sharded over the
    ('kv','hd') serve mesh, behind one router — token-identical to the
    plain single-device engine.  Needs >1 XLA device
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``; the CI
    multidevice job); skips cleanly otherwise."""

    @pytest.fixture(scope="class")
    def mesh(self):
        import jax
        if jax.device_count() < 2:
            pytest.skip("needs >1 XLA device; set XLA_FLAGS="
                        "--xla_force_host_platform_device_count=8")
        from repro.launch.mesh import make_host_serve_mesh
        from repro.configs import get_config
        cfg = get_config("qwen2-7b", reduced=True)
        return make_host_serve_mesh(cfg.num_kv_heads, cfg.head_dim)

    # inherited test_n2_token_identity_vs_single_engine runs unsharded as
    # a baseline inside this class too; the sharded variant is the point:
    def test_n2_sharded_token_identity_vs_single_engine(self, real_setup,
                                                        mesh):
        cfg = real_setup[0]
        reqs = self._workload(cfg, n=5, seed=9)
        ref_done = self._reference(real_setup, reqs)
        router, engines = self._router_over(real_setup, n=2, mesh=mesh)
        for r in reqs:
            router.submit(copy.deepcopy(r))
        done = router.run()
        assert outputs(done) == outputs(ref_done)
        assert statuses(done) == statuses(ref_done)
        for eng in engines:
            assert len(eng.executor.kv.k_pools.sharding.device_set) > 1
            eng.executor.check_sharding_invariants()
        for i in range(2):
            assert router.counters.get(f"placements_replica{i}") > 0
        router.check_invariants()


class TestRunBudgetBoundary:
    def _probe(self, max_horizon):
        sched, plane = make_replica(max_horizon=max_horizon)
        sched.submit(to_internal(req(0, plen=6, max_new=5)))
        clocks = [0]
        while sched.has_work and sched.step_i < 100:
            plane.tick(len(clocks))
            sched.step_plane()
            clocks.append(sched.step_i)
        assert not sched.has_work
        return clocks

    @pytest.mark.parametrize("max_horizon", [1, 8])
    def test_retire_on_final_tick_is_reported_in_done(self, max_horizon):
        """``run(max_steps)`` budget boundary: a request retiring exactly
        on the last permitted tick IS in ``done``; one tick less and it
        is not (the budget really binds).  Parametrized over the fused
        horizon because commit_decode advances the clock in token-steps
        mid-engine-step."""
        clocks = self._probe(max_horizon)
        final, before_final = clocks[-1], clocks[-2]
        sched, plane = make_replica(max_horizon=max_horizon)
        sched.submit(to_internal(req(0, plen=6, max_new=5)))
        # Engine.run loop verbatim: budget that admits the final step
        while sched.has_work and sched.step_i < before_final + 1:
            sched.step_plane()
        assert 0 in sched.done and sched.done[0].status == "done"
        assert len(sched.done[0].output) == 5
        assert sched.step_i == final
        # one tick less: the final step must NOT have run
        sched2, plane2 = make_replica(max_horizon=max_horizon)
        sched2.submit(to_internal(req(0, plen=6, max_new=5)))
        while sched2.has_work and sched2.step_i < before_final:
            sched2.step_plane()
        assert 0 not in sched2.done and sched2.has_work

    def test_router_run_budget_boundary(self):
        reqs = [req(i, plen=6, max_new=5) for i in range(3)]
        probe, probe_planes = make_router(2)
        for r in reqs:
            probe.submit(copy.deepcopy(r))
        probe.run(max_steps=10_000)
        final = max(rep.scheduler.step_i for rep in probe.replicas)
        assert not probe.has_work

        router, planes = make_router(2)
        for r in reqs:
            router.submit(copy.deepcopy(r))
        done = router.run(max_steps=final)
        assert statuses(done) == statuses(probe.done)
        assert not router.has_work

        # the budget really binds: with fusion disabled (one token-step
        # per engine step) one step cannot finish a 5-token request
        short, _ = make_router(2, max_horizon=1)
        for r in reqs:
            short.submit(copy.deepcopy(r))
        short.run(max_steps=1)
        assert short.has_work
