"""Continuous-batching serving engine — architecture note.

AraOS's core architectural move is a clean split between the scalar core
that owns translation and OS policy (CVA6) and the decoupled vector
datapath that streams bursts (Ara2): the vector unit only hits peak when
the scalar side stays off its critical path.  The serving engine is that
split restated in JAX:

  **Scheduler  = the CVA6 / OS plane** (:mod:`repro.serve.scheduler`)
      Queues, admission control, victim selection, fork bookkeeping,
      page-table policy.  Pure Python/NumPy — no device arrays — and
      unit-testable without a device.  It drives the data plane through
      the narrow :class:`~repro.serve.scheduler.DataPlane` protocol.

  **Executor  = the Ara2 data plane** (:mod:`repro.serve.executor`)
      All device state: the paged KV pools, a *persistent device page
      table* updated incrementally from ``VirtualMemory`` dirty-row
      deltas (never re-uploaded wholesale), and jitted prefill /
      continuation-prefill / decode steps with donated KV pools (in-place
      updates).  Spill/restore move only the victim sequence's pages
      (``ContextSwitcher.spill_kv`` — the paper's §3.1 context-switch
      cost, measured in actually-moved bytes).

  **Engine  (this module)**
      A thin facade that wires the two together and keeps the seed
      engine's public surface (``submit``/``run``/``step``/``stats``/
      ``preload_prefix``).  Requests flow
      queued -> running -> (swapped <->) running -> done.

Responsibilities mapped from the paper: page-table ownership and
on-demand allocation (MMU + OS kernel); page faults during decode with
precise accounting; preemption when the pool is exhausted (§3.1 context
switch); scheduler quanta and tick accounting (100 Hz analogue); perf
counters and host spans on the profiler's clock (the paper's measurement
infrastructure).

**Fused multi-step decode (the amortization contract on the decode
loop).**  AraOS's result is that VM overhead stays under 3.5% only
because translation is paid once per page-bounded burst, not once per
element.  The decode loop restates that per token: instead of one host
round-trip per generated token (dispatch one step, sync the sampled
token to host, replan pages, re-upload the token), the Scheduler
computes a safe horizon K — collapsed to 1 whenever a queued admission
or restore could become due mid-horizon, or when the pool cannot
pre-fault all K steps of growth — pre-faults every page the horizon will
touch in ONE batched allocation (``VirtualMemory.append_tokens_batch``,
one dirty-row flush), and the Executor runs K chained decode steps in a
single dispatch with ON-DEVICE sampling and per-lane retire masking
(``Executor.decode_multi``).  The scalar/OS plane intervenes once per
horizon: ``counters["host_syncs"] / counters["decode_tokens"]`` is the
measured amortization (the ``benchmarks/run.py --only serve`` gate
requires it < 1.0).  K=1 reproduces pre-horizon behavior exactly.

**Radix prefix layer** (:mod:`repro.serve.prefix_cache`): the Scheduler
keeps a page-granularity radix trie over the token content of resident
mapped runs — the preloaded prefix, every committed prompt, every fork
child.  A plain admission whose prompt's leading whole pages match a
registered run COW-maps those pages from the owner (the same
``fork_seq`` refcount machinery explicit forks use) and prefills only
the divergent chunk through ``admit_forked_batch``'s batched
continuation dispatch.  Token streams are identical to cold admission —
causal KV content is a pure function of the token prefix — which the
prefix bench gate (``benchmarks/run.py --only prefix``) asserts while
requiring >50% of prefill tokens skipped on a multi-turn chat workload.

The device pool reserves its LAST frame as scratch for masked decode
lanes: the engine hands ``VirtualMemory`` one frame fewer than physically
allocated.  The frozen pre-split implementation lives in
:mod:`repro.serve.reference` for equivalence tests and benchmarks.

**Multi-replica layering.**  One engine is one replica: the
:class:`~repro.serve.router.ReplicaRouter` places requests from a global
admission queue across N of these (fork affinity, least-loaded-pages or
round-robin) and drives each replica's Scheduler through the same
:meth:`~repro.serve.scheduler.Scheduler.step_plane` loop this engine's
``step`` delegates to.  Replicas share no mutable state — the N=1 router
is call-for-call this engine.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

from repro.core import CostModel, PerfCounters, VirtualMemory, VMemConfig
from repro.models.transformer import TransformerLM
from repro.serve.api import ServeRequest, ServeResult, to_internal
from repro.serve.detokenize import AsyncDetokenizer
from repro.serve.executor import Executor
from repro.serve.scheduler import Request, Scheduler, ServeConfig

__all__ = ["Engine", "Request", "ServeConfig", "ServeRequest", "ServeResult"]


def _lower(req: ServeRequest, next_id: Callable[[], int],
           cfg: ServeConfig) -> Request:
    """Lower a client submission to the scheduler-plane :class:`Request`.

    :class:`~repro.serve.api.ServeRequest` is the ONLY accepted public
    type.  The scheduler-plane :class:`Request` stays public for fake-
    plane harnesses — which construct it and call ``Scheduler.submit``
    directly — but submitting one here is a hard :class:`TypeError` (the
    one-PR deprecation shim is gone)."""
    if not isinstance(req, ServeRequest):
        raise TypeError(
            f"Engine/ReplicaRouter.submit takes a repro.serve.api."
            f"ServeRequest, got {type(req).__name__}; scheduler-plane "
            "harnesses submit internal Requests via Scheduler.submit"
        )
    rid = req.req_id if req.req_id is not None else next_id()
    return to_internal(req, req_id=rid, cfg=cfg)


class Engine:
    """Continuous batching over a paged-KV transformer (Scheduler+Executor)."""

    def __init__(self, model: TransformerLM, params: Any, cfg: ServeConfig,
                 cost: CostModel | None = None, mesh=None,
                 detokenize: Callable[[Any], str] | None = None):
        """``mesh``: optional ('kv', 'hd') serve mesh
        (:func:`repro.launch.mesh.make_host_serve_mesh`); when omitted it
        is resolved from ``cfg.serve_mesh`` (:meth:`ServeConfig.build_mesh`).
        Only the Executor's device state shards over it; the Scheduler is
        pure host policy and needs no changes — that was the point of the
        split.  ``detokenize``: token->text hook for the async stream
        thread (defaults to the id-rendering placeholder)."""
        self.model = model
        self.params = params
        self.cfg = cfg
        self.cost = cost or CostModel()
        self.counters = PerfCounters()
        if mesh is None:
            mesh = cfg.build_mesh(model.cfg)
        # the device pool has num_pages frames; the allocator sees one less
        # (last frame = scratch for masked writes)
        self.vmem = VirtualMemory(VMemConfig(
            page_size=cfg.page_size,
            num_pages=cfg.num_pages - 1,
            max_pages_per_seq=cfg.max_pages_per_seq,
            max_seqs=cfg.max_batch,
        ))
        self.scheduler = Scheduler(cfg, self.vmem, self.cost, self.counters)
        self.executor = Executor(model, params, cfg, self.vmem, self.cost,
                                 self.counters, mesh=mesh)
        self.scheduler.attach_plane(self.executor)
        #: async detokenize/stream thread (lazy: requests without a
        #: stream_callback never spawn it)
        self.detok = AsyncDetokenizer(detokenize, counters=self.counters)
        self.scheduler.attach_stream(self.detok)
        self._next_req_id = 0

    # ------------------------------------------------------------------
    # compat surface (seed engine attribute layout)
    # ------------------------------------------------------------------

    @property
    def switcher(self):
        return self.executor.switcher

    @property
    def kv(self):
        return self.executor.kv

    @property
    def queue(self):
        return self.scheduler.queue

    @property
    def running(self):
        return self.scheduler.running

    @property
    def swapped(self):
        return self.scheduler.swapped

    @property
    def done(self):
        return self.scheduler.done

    @property
    def PREFIX_ID(self):
        return self.scheduler.PREFIX_ID

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def preload_prefix(self, prefix_tokens: np.ndarray) -> None:
        """Prefill a resident shared prefix (system-prompt caching).

        Subsequent ``submit(req, share_prefix=True)`` requests fork their
        page tables from it: whole prefix pages are shared by refcount,
        only the partial tail page is copied.  The prefix also enters the
        scheduler's radix cache (AFTER its KV is committed here), so plain
        requests whose prompts merely START with the prefix content share
        its whole pages automatically — no fork API needed.
        """
        assert self.vmem.num_seqs == 0, "preload before serving"
        n = len(prefix_tokens)
        self.vmem.map_seq(self.scheduler.PREFIX_ID, n)
        slot = self.vmem.seq(self.scheduler.PREFIX_ID).slot
        self.executor.preload_prefix(np.asarray(prefix_tokens, np.int32),
                                     slot, n)
        self.scheduler.prefix_len = n
        self.scheduler.register_resident(
            self.scheduler.PREFIX_ID, np.asarray(prefix_tokens, np.int32)
        )

    def _alloc_req_id(self) -> int:
        rid = self._next_req_id
        self._next_req_id += 1
        return rid

    def submit(self, req: ServeRequest) -> int:
        """Enqueue a :class:`~repro.serve.api.ServeRequest` — the one
        public client type (anything else is a ``TypeError``).  Returns
        the request id."""
        internal = _lower(req, self._alloc_req_id, self.cfg)
        self._next_req_id = max(self._next_req_id, internal.req_id + 1)
        self.scheduler.submit(internal)
        return internal.req_id

    def run(self, max_steps: int = 10_000) -> dict[int, Request]:
        """Drive until all submitted requests complete."""
        while self.scheduler.has_work and self.scheduler.step_i < max_steps:
            self.step()
        return self.scheduler.done

    def drain(self, max_steps: int = 10_000) -> dict[int, ServeResult]:
        """Drive to completion, flush the async stream thread (re-raising
        any callback exception), and return typed
        :class:`~repro.serve.api.ServeResult` records by request id."""
        self.run(max_steps)
        self.detok.drain()
        return {
            rid: ServeResult.from_request(r)
            for rid, r in self.scheduler.done.items()
        }

    def close(self) -> None:
        """Retire the stream thread deterministically (idempotent)."""
        self.detok.close()

    def step(self) -> None:
        # the canonical serving step lives on the Scheduler
        # (``step_plane``): restore -> admit/prefill -> fused-horizon
        # decode -> commit, driven through the DataPlane protocol.  The
        # multi-replica router (repro.serve.router) drives the same loop
        # once per replica — this engine IS its N=1 instance.
        self.scheduler.step_plane()

    def as_replica(self, replica_id: int):
        """This engine as one replica of a
        :class:`~repro.serve.router.ReplicaRouter` (its Scheduler and
        Executor are already wired and share one counter set)."""
        from repro.serve.router import Replica
        return Replica.from_engine(self, replica_id)

    # ------------------------------------------------------------------
    # stats
    # ------------------------------------------------------------------

    def stats(self) -> dict[str, Any]:
        rep = self.counters.report()
        rep["switch_stats"] = dataclasses.asdict(self.executor.switcher.stats)
        rep["pool"] = {
            "frames": self.vmem.pool.num_pages,
            "free": self.vmem.pool.num_free,
            "faults": self.vmem.pool.fault_count,
        }
        rep["modeled_ctx_switch_seconds"] = (
            self.executor.switcher.stats.modeled_seconds(self.cost)
        )
        return rep
