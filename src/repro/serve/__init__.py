"""Serving: continuous batching over paged virtual memory (the "OS").

Split per the AraOS architecture, one layer per plane:

  **Router -> Scheduler(ReplicaState) -> DataPlane.**
  :class:`ReplicaRouter` (:mod:`repro.serve.router`) is the multi-replica
  control plane: it owns the global admission queue and places requests
  over N replicas (fork-affinity keeps COW forks on the prefix-holding
  replica; least-loaded-pages / round-robin rank the rest).  Each replica
  is a :class:`Scheduler` — the host-side CVA6/OS plane (policy, no
  device arrays), with every piece of per-replica mutable state factored
  into :class:`ReplicaState` — driving a :class:`DataPlane`: in
  production the device-resident :class:`Executor` (optionally sharded
  over a ('kv','hd') mesh), in tests a host-only fake.  Replicas share no
  mutable state, and the single-replica :class:`Engine` (the thin
  Scheduler+Executor facade) is exactly the N=1 instance of the layering:
  a one-replica router with the default unbounded backlog is
  call-for-call, token-for-token the plain engine — the equivalence the
  router test suite gates on for N in {1, 2, 4}.

  **Radix prefix layer.**  Each Scheduler carries a
  :class:`PrefixCache` (:mod:`repro.serve.prefix_cache`) — a
  page-granularity radix trie over the token content of resident mapped
  runs.  Admissions whose prompts share leading whole pages with a
  registered run COW-map those pages automatically (no fork API) and
  prefill only the divergent chunk; the router generalizes fork affinity
  into an additive longest-matching-prefix score when ranking replicas.

  **The portable-swap contract.**  A preempted request's swap record is
  pure host memory in the pool's storage dtype (int8 pools stay narrow)
  plus a pinned-prefix provenance carried as a page COUNT — nothing in it
  references the pool that spilled it.  That makes residency a POLICY
  decision rather than a property of whichever data plane held the
  pages: the router migrates a starved or about-to-fail swap victim to
  any replica whose pinned-prefix-adjusted demand fits
  (``Scheduler.export_swapped`` / ``import_swapped`` over
  ``DataPlane.export_swap`` / ``import_swap``, counted as
  ``restore_migrations``), re-resolving the prefix re-share claim against
  the destination's own mapping.  When even the migrated victim's
  unshared tail cannot fit anywhere all at once, the scheduler restores
  the longest page-aligned prefix that does fit and re-enqueues the
  request to re-prefill only the evicted tail through the continuation
  path (``partial_restores`` / ``pages_refilled``) — so the "failed as
  unreachable" verdict survives only when NO replica could ever host the
  request.

  **The public client API** (:mod:`repro.serve.api`) is the SUPPORTED
  entrypoint: build a validated :class:`ServeConfig` (one flag surface —
  ``ServeConfig.add_args``/``from_args``/``describe``), construct an
  :class:`Engine` (or a :class:`ReplicaRouter` over N of them), then
  ``submit()`` typed :class:`ServeRequest` records and ``drain()`` typed
  :class:`ServeResult` records — tokens, terminal status, per-request
  TTFT/TPOT timestamps captured at the scheduler's host-visible commit
  points, peak page footprint.  Per-token streaming rides an optional
  ``stream_callback``, invoked in global commit order by the
  :class:`AsyncDetokenizer` background thread (:mod:`repro.serve.
  detokenize`) so host post-processing overlaps device work; callback
  exceptions surface on ``drain()``.  The internal scheduler-plane
  :class:`Request` remains public for fake-plane harnesses — they build
  it and drive ``Scheduler.submit`` directly — but submitting it to an
  Engine/Router is a hard ``TypeError`` (the one-PR deprecation shim is
  gone).  With ``ServeConfig.aot_buckets`` the Executor
  pre-compiles bucketed prefill/continuation executables at build time so
  no request pays a first-hit jit stall, with plain prefill compiled per
  rung of a row ladder (``executor.row_buckets``) so an admission of n
  rows pads to the smallest rung that holds it, not to ``max_batch``
  (``aot_hits``/``aot_misses``/``bucket_pad_tokens``/
  ``prefill_pad_rows``; the open-loop SLO gate in
  ``benchmarks/bench_serve_slo.py`` holds ``aot_misses == 0``).

:class:`ReferenceEngine` is the frozen pre-split seed implementation kept
for equivalence testing and before/after benchmarks.
"""
from repro.serve.api import (
    RequestTiming,
    SamplingParams,
    ServeRequest,
    ServeResult,
    StreamEvent,
    to_internal,
)
from repro.serve.detokenize import AsyncDetokenizer
from repro.serve.engine import Engine
from repro.serve.executor import Executor
from repro.serve.prefix_cache import PrefixCache
from repro.serve.reference import ReferenceEngine
from repro.serve.router import Replica, ReplicaRouter
from repro.serve.scheduler import (
    DataPlane,
    DecodePlan,
    HostOnlyPlane,
    ReplicaState,
    Request,
    RestoreFailure,
    Scheduler,
    ServeConfig,
    SwapExport,
)

__all__ = [
    "AsyncDetokenizer",
    "DataPlane",
    "DecodePlan",
    "Engine",
    "Executor",
    "HostOnlyPlane",
    "PrefixCache",
    "ReferenceEngine",
    "Replica",
    "ReplicaRouter",
    "ReplicaState",
    "Request",
    "RequestTiming",
    "RestoreFailure",
    "SamplingParams",
    "Scheduler",
    "ServeConfig",
    "ServeRequest",
    "ServeResult",
    "StreamEvent",
    "SwapExport",
    "to_internal",
]
