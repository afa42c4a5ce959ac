"""Device-resident serving executor — the Ara2 data plane of the split.

Everything that touches a device array lives here: the paged KV pools, a
*persistent device page table* (the satp analogue, updated incrementally
from ``VirtualMemory.drain_dirty_rows()`` deltas — never re-uploaded
wholesale), and jitted prefill / continuation-prefill / decode steps whose
KV pools are donated so XLA updates them in place.

Contrast with the seed engine's hot path, which re-uploaded the full page
table every decode step and stacked+reshaped both full KV pools on every
spill/restore.  Here:

  * page-table updates are delta-only (``ptab_rows_uploaded`` counter);
  * spill/restore move only the victim sequence's pages
    (``ContextSwitcher.spill_kv``/``restore_kv`` — page-granular, the
    paper's §3.1 context-switch cost in actually-moved bytes);
  * inactive decode lanes are masked *inside* the jitted step from a [B]
    bool mask, not by rewriting table rows on the host;
  * decode runs in fused K-step horizons (``decode_multi``): one dispatch
    chains K ``decode_step``s with on-device sampling (greedy argmax or
    temperature/categorical with a threaded PRNG key) and per-lane retire
    masking, so the host round-trip — and the page-table delta sync — is
    paid once per horizon, not once per token (``host_syncs`` /
    ``decode_horizon`` counters);
  * with a ('kv', 'hd') serve mesh the whole device state SHARDS: KV
    pools partition jointly over KV heads and head_dim
    (``launch.specs.executor_state_shardings``), the page table and every
    scalar-plane operand replicate, and all jitted dispatches carry
    explicit ``in_shardings``/``out_shardings`` with donated pools so the
    fused decode horizon runs sharded for free — the Ara2 analogue of
    scaling lanes/cores under one shared, coherent translation structure;
  * the Pallas kernels stay LIVE on that mesh: a kernel-built model is
    rebound to a mesh twin (``_mesh_kernel_model``) whose serve paths
    shard_map every paged-attention/paged-copy call onto per-device pool
    slices — KV-head shards attend independently (per-head online
    softmax: no collective), head_dim shards all-gather K/V inside the
    shard body, the replicated page table translates without
    communication (specs per operand: ``kernels/ops.py``).  The jnp twin
    survives only as the explicit ``ServeConfig.use_ref_path`` escape
    hatch; every compute step is tallied as ``kernel_dispatches`` vs
    ``ref_path_dispatches`` so any fallback is loud.

The executor implements the scheduler's :class:`~repro.serve.scheduler.
DataPlane` protocol — both the movement surface (spill/restore/discard/
fork) and the compute surface (prefill/decode/decode_multi) that
``Scheduler.step_plane`` drives — and makes no policy decisions.  One
executor is one replica's data plane: the multi-replica router
(:mod:`repro.serve.router`) runs N of these behind one admission
front-end, each with its own KV pools, page table and page pool (no
cross-replica device state).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    ContextSwitcher,
    CostModel,
    INVALID_PAGE,
    PerfCounters,
    VirtualMemory,
)
from repro.models.transformer import PagedKVState, TransformerLM
from repro.serve.scheduler import DecodePlan, Request, ServeConfig


# ---------------------------------------------------------------------------
# device-step bodies
#
# Plain functions, jitted twice below: once at module level for the
# single-device executor (shared cache per model, exactly the pre-mesh
# behavior) and once per (model, mesh) for the sharded executor, with
# explicit ``in_shardings``/``out_shardings`` so the KV pools stay laid
# out over the ('kv', 'hd') serve mesh across donated in-place updates.
# ---------------------------------------------------------------------------


def _ptab_delta_impl(ptab: jax.Array, rows: jax.Array,
                     vals: jax.Array) -> jax.Array:
    """Scatter dirty rows into the persistent device page table."""
    return ptab.at[rows].set(vals)


def _prefill_impl(model: TransformerLM, params: Any, tokens: jax.Array,
                  lens: jax.Array, k_pools: jax.Array, v_pools: jax.Array,
                  pt_rows: jax.Array):
    state = PagedKVState(k_pools, v_pools, pt_rows,
                         jnp.zeros_like(lens))
    logits, ns = model.prefill(params, tokens, lens, state)
    return logits, ns.k_pools, ns.v_pools


def _continue_impl(model: TransformerLM, params: Any, tokens: jax.Array,
                   starts: jax.Array, lens: jax.Array, k_pools: jax.Array,
                   v_pools: jax.Array, pt_rows: jax.Array):
    state = PagedKVState(k_pools, v_pools, pt_rows,
                         jnp.zeros_like(starts))
    logits, ns = model.prefill_continue(params, tokens, starts, lens, state)
    return logits, ns.k_pools, ns.v_pools


def _decode_impl(model: TransformerLM, params: Any, tokens: jax.Array,
                 k_pools: jax.Array, v_pools: jax.Array, ptab: jax.Array,
                 pre_lens: jax.Array, active: jax.Array):
    # mask page-table rows of slots that are NOT decoding this step:
    # mapped-but-idle sequences (e.g. the resident shared prefix) must not
    # receive the inactive-lane scratch writes — with a valid row the guard
    # would route them into a LIVE frame instead of the reserved scratch
    # row.  The mask is applied on device from a [B] bool vector; the table
    # itself is never rewritten.
    masked = jnp.where(active[:, None], ptab, INVALID_PAGE)
    state = PagedKVState(k_pools, v_pools, masked, pre_lens)
    logits, ns = model.decode_step(params, tokens, state)
    return logits, ns.k_pools, ns.v_pools


def _decode_multi_impl(model: TransformerLM, params: Any, tokens: jax.Array,
                       k_pools: jax.Array, v_pools: jax.Array,
                       ptab: jax.Array, pre_lens: jax.Array,
                       steps_left: jax.Array, rng: jax.Array,
                       temperature: jax.Array, horizon: int, greedy: bool):
    """Fused K-step decode horizon with ON-DEVICE sampling.

    One dispatch runs ``horizon`` chained ``model.decode_step`` calls
    (``lax.scan`` inside :meth:`TransformerLM.decode_multi_step`), sampling
    each next token on device and feeding it straight back — the host
    round-trip per token (sample transfer, replan, token re-upload)
    becomes one round-trip per horizon.  Per-lane retirement is masked on
    device from ``steps_left``; the page table is read-only (masking
    happens per inner step, the table itself is never rewritten).
    """
    state = PagedKVState(k_pools, v_pools, ptab, pre_lens)
    block, ns, rng = model.decode_multi_step(
        params, tokens, state, steps_left, rng, temperature,
        horizon=horizon, greedy=greedy,
    )
    return block, ns.k_pools, ns.v_pools, rng


def _copy_pages_impl(k_pools: jax.Array, v_pools: jax.Array, srcs: jax.Array,
                     dsts: jax.Array):
    """COW tail-page copies: all forked frames in each pool, one dispatch."""
    return (k_pools.at[:, dsts].set(k_pools[:, srcs]),
            v_pools.at[:, dsts].set(v_pools[:, srcs]))


# single-device jit cache (module-level so it is shared per model)
_apply_ptab_delta = jax.jit(_ptab_delta_impl, donate_argnums=(0,))
_prefill_step = jax.jit(_prefill_impl, static_argnums=(0,),
                        donate_argnums=(4, 5))
_continue_step = jax.jit(_continue_impl, static_argnums=(0,),
                         donate_argnums=(5, 6))
_decode_step = jax.jit(_decode_impl, static_argnums=(0,),
                       donate_argnums=(3, 4))
_decode_multi_step = jax.jit(_decode_multi_impl, static_argnums=(0, 10, 11),
                             donate_argnums=(3, 4))
_copy_pages = jax.jit(_copy_pages_impl, donate_argnums=(0, 1))


def select_bucket(n: int, buckets: tuple[int, ...] | None) -> int | None:
    """Smallest AOT bucket that can hold an ``n``-token (burst-aligned)
    prompt batch, or ``None`` on a miss — the caller then falls back to
    the shape-keyed jit path.  ``buckets`` is a sorted tuple: the
    ``ServeConfig.aot_buckets`` lengths, or a :func:`row_buckets` ladder
    for an ``n``-row admission."""
    if not buckets:
        return None
    for b in buckets:
        if b >= n:
            return b
    return None


def row_buckets(max_batch: int) -> tuple[int, ...]:
    """Row counts the AOT prefill programs are compiled for: the powers
    of 4 below ``max_batch``, then ``max_batch`` itself — ``(1, 4, 16)``
    for 16 lanes, ``(1, 4, 16, 64)`` for 64.  An admission of ``n`` rows
    runs the smallest that holds it, so a lone arrival is a one-row
    program, not a ``max_batch``-row one.  Powers of 4 rather than 2:
    each rung adds one compiled program per length bucket to build."""
    rows, r = [], 1
    while r < max_batch:
        rows.append(r)
        r *= 4
    return (*rows, max_batch)


#: AOT-compiled prefill/continuation executables, keyed by
#: (step-model twin, mesh, kind, rows, bucket, batch/pool geometry).  Module
#: level — mirroring the lru_cached model twins above — so every engine
#: over the same (model twin, mesh, geometry) binds the SAME compiled
#: executable instead of re-lowering at each build.
_AOT_CACHE: dict[tuple, Any] = {}


@functools.lru_cache(maxsize=None)
def _ref_path_model(model: TransformerLM) -> TransformerLM:
    """Explicit jnp escape hatch (``ServeConfig.use_ref_path``).

    A shallow copy with ``use_kernels=False`` — the jnp reference paths,
    which GSPMD partitions freely.  This used to be the *implicit* dispatch
    for every kernel model under a >1-device mesh; the shard_map wrappers
    in ``kernels.ops`` made that fallback unnecessary, so the twin remains
    only behind the explicit config flag (``--no-kernels`` in
    ``launch.serve``), and every compute step through it is counted as
    ``ref_path_dispatches``.  Cached per model so every engine over the
    same model shares the twin's jit traces.
    """
    import copy
    twin = copy.copy(model)
    twin.use_kernels = False
    return twin


@functools.lru_cache(maxsize=None)
def _kv_dtype_model(model: TransformerLM, kv_dtype: str) -> TransformerLM:
    """KV-storage twin (``ServeConfig.kv_dtype``).

    A shallow copy with ``kv_dtype`` rebound: ``init_kv_state`` then
    allocates quantized pools and every serve path quantizes its writes /
    passes ``kv_scale`` into the paged-attention kernels (which dequantize
    in VMEM — int8 no longer routes to the ref path).  Composes with the
    other twins: the ref-path and mesh rebinds below copy whatever model
    they are handed, so the storage dtype survives them.  Cached per
    (model, dtype) so engines over the same pair share jit traces.
    """
    import copy
    twin = copy.copy(model)
    twin.kv_dtype = kv_dtype
    return twin


@functools.lru_cache(maxsize=None)
def _mesh_kernel_model(model: TransformerLM, mesh) -> TransformerLM:
    """Mesh-bound kernel twin: the Pallas paths stay LIVE under sharding.

    A shallow copy with ``kernel_mesh=mesh``: the model's serve paths then
    dispatch paged attention / paged copies through the shard_map wrappers
    in ``kernels.ops``, where each device runs the unmodified kernel on
    its local KV-pool slice (KV-head sharding is collective-free; head_dim
    sharding all-gathers K/V inside the shard body — see the ops module
    docstring for the per-operand specs).  Cached per (model, mesh) so
    engines over the same pair share jit traces, mirroring
    ``_sharded_steps``.
    """
    import copy
    twin = copy.copy(model)
    twin.kernel_mesh = mesh
    return twin


@functools.lru_cache(maxsize=None)
def _executor_shardings(mesh, num_kv_heads: int, head_dim: int):
    """(pool, replicated) NamedShardings for an executor on ``mesh``.

    Imported lazily: ``launch.specs`` pulls the full dry-run surface
    (configs, optimizer, train step), which plain single-device serving
    never needs.
    """
    from repro.launch.specs import executor_state_shardings
    sh = executor_state_shardings(mesh, num_kv_heads, head_dim)
    return sh["pool"], sh["replicated"]


@functools.lru_cache(maxsize=None)
def _sharded_steps(model: TransformerLM, mesh):
    """Per-(model, mesh) jitted steps with explicit sharding contracts.

    The model is bound via ``partial`` (it is a static self argument) so
    ``in_shardings`` maps 1:1 onto the dynamic args.  Pools shard over the
    ('kv', 'hd') mesh axes and are donated — XLA updates them in place,
    shard-local; everything the scalar/OS plane produces or consumes
    (page-table rows, tokens, positions, logits, the sampled block) is
    replicated, the satp analogue every shard reads coherently.
    """
    pool, rep = _executor_shardings(
        mesh, model.cfg.num_kv_heads, model.cfg.head_dim
    )
    return {
        "ptab": jax.jit(_ptab_delta_impl, in_shardings=(rep, rep, rep),
                        out_shardings=rep, donate_argnums=(0,)),
        "prefill": jax.jit(
            functools.partial(_prefill_impl, model),
            in_shardings=(rep, rep, rep, pool, pool, rep),
            out_shardings=(rep, pool, pool), donate_argnums=(3, 4),
        ),
        "continue": jax.jit(
            functools.partial(_continue_impl, model),
            in_shardings=(rep, rep, rep, rep, pool, pool, rep),
            out_shardings=(rep, pool, pool), donate_argnums=(4, 5),
        ),
        "decode": jax.jit(
            functools.partial(_decode_impl, model),
            in_shardings=(rep, rep, pool, pool, rep, rep, rep),
            out_shardings=(rep, pool, pool), donate_argnums=(2, 3),
        ),
        "copy_pages": jax.jit(
            _copy_pages_impl, in_shardings=(pool, pool, rep, rep),
            out_shardings=(pool, pool), donate_argnums=(0, 1),
        ),
    }


@functools.lru_cache(maxsize=None)
def _sharded_decode_multi(model: TransformerLM, mesh, horizon: int,
                          greedy: bool):
    """Sharded fused-horizon dispatch; cached per (model, mesh, K, greedy)
    — the horizon ladder is O(log max_horizon) powers of two, so this
    cache stays as small as the single-device one."""
    pool, rep = _executor_shardings(
        mesh, model.cfg.num_kv_heads, model.cfg.head_dim
    )
    return jax.jit(
        functools.partial(_decode_multi_impl, model, horizon=horizon,
                          greedy=greedy),
        in_shardings=(rep, rep, pool, pool, rep, rep, rep, rep, rep),
        out_shardings=(rep, pool, pool, rep), donate_argnums=(2, 3),
    )


class Executor:
    """Owns KV pools + the device page table; executes scheduler plans.

    With ``mesh`` (a ('kv', 'hd') serve mesh, see
    :func:`repro.launch.mesh.make_host_serve_mesh`) the KV pools shard
    jointly over KV heads and head_dim while the page table and every
    scalar-plane operand replicate — the Scheduler needs no changes, which
    is the point of the split.  All dispatches carry explicit
    ``in_shardings``/``out_shardings`` with donated pools, so spill /
    restore / COW-fork / ptab-delta updates preserve the layout;
    :meth:`check_sharding_invariants` asserts that after every mutation.
    """

    def __init__(self, model: TransformerLM, params: Any, cfg: ServeConfig,
                 vmem: VirtualMemory, cost: CostModel | None = None,
                 counters: PerfCounters | None = None, mesh=None):
        kv_dtype = getattr(cfg, "kv_dtype", "native")
        if kv_dtype != "native" and getattr(
                model, "kv_dtype", "native") != kv_dtype:
            # quantized-pool twin FIRST: pool allocation below and every
            # later twin (ref path, mesh) derive from it, so the storage
            # dtype is a single config knob
            model = _kv_dtype_model(model, kv_dtype)
        self.model = model
        self.params = params
        self.cfg = cfg
        self.vmem = vmem
        self.counters = counters or PerfCounters()
        self.switcher = ContextSwitcher(vmem, cost, page_axis=1)
        # the device pool has num_pages frames; the allocator saw one less
        # (last frame = scratch for masked lanes)
        self.kv = model.init_kv_state(
            cfg.max_batch, cfg.num_pages, cfg.page_size, cfg.max_pages_per_seq
        )
        #: persistent satp: updated by delta scatter, read by every step
        self._ptab = jnp.full(
            (cfg.max_batch, cfg.max_pages_per_seq), INVALID_PAGE, jnp.int32
        )
        self._rng = jax.random.PRNGKey(cfg.seed)
        self.mesh = mesh
        self._pool_sh = self._rep_sh = None
        self._step_model = model
        #: True iff compute steps dispatch through a use_kernels=False
        #: twin of a kernel-built model (the explicit escape hatch) —
        #: counted per dispatch as ``ref_path_dispatches``
        self._ref_path = False
        if getattr(cfg, "use_ref_path", False) and getattr(
                model, "use_kernels", False):
            self._step_model = _ref_path_model(model)
            self._ref_path = True
        if mesh is not None:
            if mesh.size > 1 and getattr(
                    self._step_model, "use_kernels", False):
                # kernels stay LIVE under the mesh: the twin binds the
                # mesh so the serve paths shard_map every Pallas call
                # onto per-device pool slices (kernels/ops.py)
                self._step_model = _mesh_kernel_model(self._step_model,
                                                      mesh)
            self._pool_sh, self._rep_sh = _executor_shardings(
                mesh, model.cfg.num_kv_heads, model.cfg.head_dim
            )
            self._steps = _sharded_steps(self._step_model, mesh)
            # commit the persistent state to its declared layout; params
            # replicate (TP of the weights is the dry-run serving view's
            # job — the executor's contract is the KV/page-table state)
            self.params = jax.device_put(params, self._rep_sh)
            self.kv = self.kv._replace(
                k_pools=jax.device_put(self.kv.k_pools, self._pool_sh),
                v_pools=jax.device_put(self.kv.v_pools, self._pool_sh),
            )
            self._ptab = jax.device_put(self._ptab, self._rep_sh)
        else:
            # same call surface as the sharded table so every dispatch
            # site below is placement-oblivious
            self._steps = {
                "ptab": _apply_ptab_delta,
                "prefill": functools.partial(_prefill_step,
                                             self._step_model),
                "continue": functools.partial(_continue_step,
                                              self._step_model),
                "decode": functools.partial(_decode_step, self._step_model),
                "copy_pages": _copy_pages,
            }
        #: row counts each AOT kind is compiled for: plain prefill takes
        #: the :func:`row_buckets` ladder; continuation keeps ``max_batch``
        self._aot_rows = {"prefill": row_buckets(cfg.max_batch),
                          "continue": (cfg.max_batch,)}
        #: AOT-bucketed prefill/continuation executables for THIS engine,
        #: (kind, rows, bucket) -> compiled; populated at build so no
        #: request ever pays a first-hit jit stall
        #: (``ServeConfig.aot_buckets``)
        self._aot: dict[tuple[str, int, int], Any] = {}
        if getattr(cfg, "aot_buckets", None):
            self._compile_aot()

    # ------------------------------------------------------------------
    # AOT-bucketed prefill (ServeConfig.aot_buckets)
    # ------------------------------------------------------------------

    def _aot_key(self, kind: str, rows: int, bucket: int) -> tuple:
        """Module-cache key: everything the compiled executable's shapes,
        dtypes and shardings derive from.  The kv-dtype / ref-path / mesh
        twins are all folded into ``self._step_model`` + ``self.mesh``, so
        distinct twins get distinct executables and identical twins share."""
        return (self._step_model, self.mesh, kind, rows, bucket,
                self.cfg.max_batch, self.cfg.num_pages,
                self.cfg.page_size, self.cfg.max_pages_per_seq)

    def _aot_operands(self, kind: str, bucket: int, rows: int) -> tuple:
        """``ShapeDtypeStruct`` operands of one bucketed dispatch: ``rows``
        rows (a rung of the prefill row ladder, ``max_batch`` for a
        continuation) of ``bucket``-length prompts, the executor's live
        pool geometry (quantized pools keep their narrow dtype because the
        SDS is read off the allocated pools) and ``rows`` page-table
        rows."""
        sds = jax.ShapeDtypeStruct
        p_sds = jax.tree_util.tree_map(
            lambda a: sds(jnp.shape(a), a.dtype), self.params
        )
        tok = sds((rows, bucket), jnp.int32)
        lens = sds((rows,), jnp.int32)
        k = sds(self.kv.k_pools.shape, self.kv.k_pools.dtype)
        v = sds(self.kv.v_pools.shape, self.kv.v_pools.dtype)
        pt = sds((rows, self.cfg.max_pages_per_seq), jnp.int32)
        if kind == "continue":
            starts = sds((rows,), jnp.int32)
            return (p_sds, tok, starts, lens, k, v, pt)
        return (p_sds, tok, lens, k, v, pt)

    def _compile_aot(self) -> None:
        """Pre-lower and ``aot_compile`` every (kind, rows, bucket)
        executable at engine build.  Single-device lowering goes through
        the module jits (the model is a static argument, baked in at lower
        time); mesh lowering goes through the per-(model, mesh) sharded
        steps so the executables carry the declared in/out shardings at
        every row count."""
        for kind, ladder in self._aot_rows.items():
            for rows in ladder:
                for bucket in self.cfg.aot_buckets:
                    key = self._aot_key(kind, rows, bucket)
                    exe = _AOT_CACHE.get(key)
                    if exe is None:
                        ops = self._aot_operands(kind, bucket, rows)
                        if self.mesh is not None:
                            exe = self._steps[kind].lower(*ops).compile()
                        elif kind == "prefill":
                            exe = _prefill_step.lower(
                                self._step_model, *ops).compile()
                        else:
                            exe = _continue_step.lower(
                                self._step_model, *ops).compile()
                        _AOT_CACHE[key] = exe
                    self._aot[(kind, rows, bucket)] = exe

    def _select_aot(self, kind: str, reqs: list[Request]):
        """The AOT executable for this batch — ``(compiled, rows,
        bucket)``: the smallest compiled row count that holds the admitted
        rows and the smallest bucket that holds their burst-aligned width;
        ``(None, len(reqs), None)`` to fall back to the shape-keyed jit.
        Hits and misses are counted only when bucketing is configured; a
        miss is a batch whose burst-aligned width exceeds every bucket (or
        a non-1D prompt modality the buckets were not compiled for)."""
        nrows = len(reqs)
        if not self._aot:
            return None, nrows, None
        page = self.cfg.page_size
        smax = max(len(r.prompt) for r in reqs)
        smax = -(-smax // page) * page
        bucket = None
        if not reqs[0].prompt.shape[1:]:     # 1-D token prompts only
            bucket = select_bucket(smax, self.cfg.aot_buckets)
        rows = select_bucket(nrows, self._aot_rows[kind])
        exe = self._aot.get((kind, rows, bucket))
        if exe is None:
            self.counters.inc("aot_misses")
            return None, nrows, None
        self.counters.inc("aot_hits")
        return exe, rows, bucket

    # ------------------------------------------------------------------
    # sharding invariants (mesh mode)
    # ------------------------------------------------------------------

    def check_sharding_invariants(self, extra=()) -> None:
        """Mesh mode: every persistent device array must still carry its
        declared layout.  The update paths that could silently reshard it
        — donated step outputs (including the shard_map kernel dispatches,
        whose claimed out specs GSPMD takes on faith with replication
        checks off), the ptab delta scatter, COW tail copies, and
        page-granular spill/restore through ``ContextSwitcher`` — all run
        between two calls of this check, so a drift (which would cost a
        full rematerialization on the next dispatch) fails loudly instead
        of showing up as a perf cliff.  ``extra`` adds transient
        ``(name, array, want)`` triples — the compute steps pass their
        kernel outputs (logits / sampled blocks) with the replicated
        sharding the step declared.  Metadata-only: no device sync."""
        if self.mesh is None:
            return
        for name, arr, want in (
            ("k_pools", self.kv.k_pools, self._pool_sh),
            ("v_pools", self.kv.v_pools, self._pool_sh),
            ("page_table", self._ptab, self._rep_sh),
        ) + tuple(extra):
            if not arr.sharding.is_equivalent_to(want, arr.ndim):
                # a real exception, not `assert`: the guard must survive
                # `python -O`, where asserts are compiled out
                raise RuntimeError(
                    f"executor {name} drifted off its declared layout: "
                    f"{arr.sharding} != {want}"
                )

    # ------------------------------------------------------------------
    # persistent device page table
    # ------------------------------------------------------------------

    def sync_page_table(self) -> None:
        """Apply host page-table deltas (dirty rows only) to the device."""
        with self.counters.span("serve.exec.ptab_sync",
                                rows=self.vmem.num_dirty_rows):
            rows, vals = self.vmem.drain_dirty_rows()
            if rows.size:
                self._ptab = self._steps["ptab"](
                    self._ptab, jnp.asarray(rows), jnp.asarray(vals)
                )
                self.counters.inc("ptab_rows_uploaded", int(rows.size))
                self.counters.inc("ptab_syncs")
                self.check_sharding_invariants()

    @property
    def device_page_table(self) -> jax.Array:
        return self._ptab

    # ------------------------------------------------------------------
    # compute steps
    # ------------------------------------------------------------------

    def _count_dispatch(self) -> None:
        """Kernel-vs-ref observability, once per compute step: the silent
        mesh fallback this counter made loud is gone, so in any gated run
        ``ref_path_dispatches`` must be 0 unless the explicit escape hatch
        (``ServeConfig.use_ref_path``) asked for the jnp twin.  Quantized
        pools dispatch the SAME kernels (dequant-in-kernel), so int8 steps
        count as ``kernel_dispatches`` too; ``quant_dispatches`` tracks
        how many compute steps ran over quantized pools regardless of
        path, making "quantization silently fell back" as observable as
        the mesh fallback was."""
        if self._ref_path:
            self.counters.inc("ref_path_dispatches")
        elif getattr(self._step_model, "use_kernels", False):
            self.counters.inc("kernel_dispatches")
        if getattr(self._step_model, "kv_dtype", "native") != "native":
            self.counters.inc("quant_dispatches")

    def _continuation_gather_bytes(self, start_lens, smax: int,
                                   nrows: int) -> int:
        """Analytical K+V bytes the continuation-prefill attention reads
        per layer stack — the paper's bytes-gathered cost model, scored
        per dispatch so ``bench_serve_sharded`` can gate the kernel's
        page-streaming win ON THE MESH.  Kernel path: only pages reachable
        under the causal clamp per query block (``pages_touched``, the
        same formula the prefill kernel's grid enforces, with the ops
        wrapper's default bq).  Ref path: the jnp oracle gathers every
        row's full table reach."""
        from repro.kernels.paged_prefill_attention import pages_touched
        cfg = self.model.cfg
        per_tok = (2 * cfg.num_kv_heads * cfg.head_dim
                   * jnp.dtype(self.kv.k_pools.dtype).itemsize)
        if getattr(self._step_model, "use_kernels", False):
            pages = sum(
                pages_touched(int(st), smax, self.cfg.max_pages_per_seq,
                              page_size=self.cfg.page_size, bq=32)
                for st in start_lens
            )
            tokens = pages * self.cfg.page_size
        else:
            tokens = nrows * self.cfg.max_pages_per_seq * self.cfg.page_size
        return cfg.num_layers * per_tok * tokens

    def _decode_multi_fn(self, horizon: int):
        """The fused-horizon dispatch for ``horizon`` (statics bound)."""
        if self.mesh is not None:
            return _sharded_decode_multi(
                self._step_model, self.mesh, horizon, self.cfg.greedy
            )
        return functools.partial(
            _decode_multi_step, self._step_model,
            horizon=horizon, greedy=self.cfg.greedy,
        )

    def preload_prefix(self, prefix_tokens: np.ndarray, slot: int,
                       n: int) -> None:
        self.sync_page_table()
        tokens = np.asarray(prefix_tokens, np.int32)[None, :]
        page = self.cfg.page_size
        pad = (-n) % page
        if pad:
            tokens = np.pad(tokens, ((0, 0), (0, pad)))
        pt_rows = jnp.take(self._ptab, jnp.asarray([slot]), axis=0)
        _, k, v = self._steps["prefill"](
            self.params, jnp.asarray(tokens),
            jnp.asarray([n], jnp.int32), self.kv.k_pools, self.kv.v_pools,
            pt_rows,
        )
        self.kv = self.kv._replace(k_pools=k, v_pools=v)
        self._count_dispatch()
        self.counters.inc("prefix_tokens", n)

    def _pad_prompt_batch(self, reqs: list[Request], bucket: int | None,
                          rows: int):
        """Burst-aligned ``[B, smax]`` prompt matrix + true lengths + the
        batch's page-table rows — shared by plain and forked admission so
        padding/slot-lookup policy cannot desynchronize between them.

        ``bucket`` and ``rows`` are what ``_select_aot`` picked.  With
        ``bucket`` (an AOT dispatch) the batch is padded to the compiled
        shape — ``rows`` rows (a rung of the row ladder) of ``bucket``
        tokens; without it ``rows`` is the admitted row count.  The
        padding is numerically inert: pad rows carry ``lens=0`` and
        all-INVALID_PAGE table rows (writes route to the scratch frame),
        pad columns sit beyond every real row's length so causal masking
        excludes them — real-row outputs are bit-identical to the
        unbucketed dispatch.  The pure overhead (padded cells minus what
        the shape-keyed dispatch would have carried) is counted as
        ``bucket_pad_tokens``, the pad rows alone as
        ``prefill_pad_rows``."""
        page = self.cfg.page_size
        smax = max(len(r.prompt) for r in reqs)
        smax = -(-smax // page) * page            # burst-align (jit reuse)
        nrows = len(reqs)
        if bucket is not None:
            self.counters.inc("bucket_pad_tokens",
                              rows * bucket - nrows * smax)
            self.counters.inc("prefill_pad_rows", rows - nrows)
            smax = bucket
        tok_shape = (rows, smax) + reqs[0].prompt.shape[1:]
        tokens = np.zeros(tok_shape, np.int32)
        for i, r in enumerate(reqs):
            tokens[i, : len(r.prompt)] = r.prompt
        lens = np.zeros((rows,), np.int32)
        lens[:nrows] = [len(r.prompt) for r in reqs]
        slots = [self.vmem.seq(r.req_id).slot for r in reqs]
        pt_rows = jnp.take(self._ptab, jnp.asarray(slots), axis=0)
        if rows > nrows:
            pt_rows = jnp.pad(pt_rows, ((0, rows - nrows), (0, 0)),
                              constant_values=INVALID_PAGE)
        return tokens, lens, pt_rows

    def prefill(self, reqs: list[Request]) -> list[np.ndarray]:
        """Batched prefill of freshly admitted requests; returns the first
        sampled token per request (request order)."""
        span = self.counters.span
        with span("serve.exec.prefill", rows=len(reqs)):
            self.sync_page_table()
            exe, rows, bucket = self._select_aot("prefill", reqs)
            with span("serve.exec.pack", bucket=bucket or 0, rows=rows):
                tokens, lens, pt_rows = self._pad_prompt_batch(
                    reqs, bucket, rows)
                tokens, lens = jnp.asarray(tokens), jnp.asarray(lens)
            fn = exe if exe is not None else self._steps["prefill"]
            with span("serve.exec.dispatch"):
                logits, k, v = fn(self.params, tokens, lens,
                                  self.kv.k_pools, self.kv.v_pools, pt_rows)
            # async dispatch returns immediately; block so the span
            # measures execution, not dispatch
            with span("serve.exec.wait"):
                jax.block_until_ready(logits)
            self.kv = self.kv._replace(k_pools=k, v_pools=v)
            self._count_dispatch()
            # kernel outputs must come back on the declared (replicated)
            # layout, not whatever GSPMD inferred through the shard_map
            self.check_sharding_invariants(
                extra=(("prefill_logits", logits, self._rep_sh),)
            )
            with span("serve.exec.readback"):
                first = self.sample(logits)
                return [np.asarray(first[i]) for i in range(len(reqs))]

    def decode(self, tokens: np.ndarray, pre_lens: np.ndarray,
               active: np.ndarray) -> np.ndarray:
        """One full-slot decode step (the horizon's K=1 collapse path);
        returns sampled tokens by slot."""
        span = self.counters.span
        with span("serve.exec.decode", horizon=1):
            self.sync_page_table()
            with span("serve.exec.pack"):
                tokens, pre_lens, active = (
                    jnp.asarray(tokens), jnp.asarray(pre_lens),
                    jnp.asarray(active))
            with span("serve.exec.dispatch"):
                logits, k, v = self._steps["decode"](
                    self.params, tokens, self.kv.k_pools, self.kv.v_pools,
                    self._ptab, pre_lens, active,
                )
            with span("serve.exec.wait"):
                jax.block_until_ready(logits)
            self.kv = self.kv._replace(k_pools=k, v_pools=v)
            self._count_dispatch()
            self.check_sharding_invariants(
                extra=(("decode_logits", logits, self._rep_sh),)
            )
            self.counters.inc("decode_dispatches")
            self.counters.inc("decode_horizon")
            with span("serve.exec.readback"):
                return self.sample(logits)

    def decode_multi(self, plan: DecodePlan) -> np.ndarray:
        """Fused K-step decode horizon: ONE dispatch runs ``plan.horizon``
        chained decode steps with on-device sampling and per-lane retire
        masking, then transfers the whole ``[K, B, ...]`` token block in
        one host sync.  ``Executor.sample``'s per-token host path does not
        run on this path.  The scheduler has already pre-faulted every page
        the horizon touches, so exactly one page-table delta sync happens
        per horizon."""
        span = self.counters.span
        with span("serve.exec.decode", horizon=plan.horizon):
            self.sync_page_table()
            with span("serve.exec.pack"):
                tokens, pre_lens, steps_left = (
                    jnp.asarray(plan.tokens), jnp.asarray(plan.pre_lens),
                    jnp.asarray(plan.steps_left))
            with span("serve.exec.dispatch"):
                block, k, v, rng = self._decode_multi_fn(plan.horizon)(
                    self.params, tokens, self.kv.k_pools, self.kv.v_pools,
                    self._ptab, pre_lens, steps_left,
                    # plain float -> weak-typed scalar under jit: logits /
                    # temperature keeps the logits dtype, exactly like the
                    # host path's division by the Python float
                    self._rng, float(self.cfg.temperature),
                )
            with span("serve.exec.wait"):
                jax.block_until_ready(block)
            self.kv = self.kv._replace(k_pools=k, v_pools=v)
            self._rng = rng
            self._count_dispatch()
            self.check_sharding_invariants(
                extra=(("decode_block", block, self._rep_sh),)
            )
            self.counters.inc("host_syncs")
            self.counters.inc("decode_dispatches")
            self.counters.inc("decode_horizon", plan.horizon)
            with span("serve.exec.readback"):
                return np.asarray(block)

    # ------------------------------------------------------------------
    # DataPlane protocol (driven by the Scheduler)
    # ------------------------------------------------------------------

    def admit_forked_batch(
        self, reqs: list[Request], start_lens: list[int],
        tail_copies: list[tuple[int, int] | None],
    ) -> list[np.ndarray]:
        """COW tail copies + ONE batched continuation prefill for all
        same-step forked admissions (each request's prompt chunk starts at
        its own logical offset) — replaces both the seed's one-token-at-a-
        time teacher forcing and the per-request B=1 continuation calls."""
        span = self.counters.span
        with span("serve.exec.continue", rows=len(reqs)):
            self.sync_page_table()
            copies = [tc for tc in tail_copies if tc is not None]
            if copies:
                with span("serve.exec.dispatch"):
                    k, v = self._steps["copy_pages"](
                        self.kv.k_pools, self.kv.v_pools,
                        jnp.asarray([src for src, _ in copies]),
                        jnp.asarray([dst for _, dst in copies]),
                    )
                self.kv = self.kv._replace(k_pools=k, v_pools=v)
            exe, rows, bucket = self._select_aot("continue", reqs)
            with span("serve.exec.pack", bucket=bucket or 0, rows=rows):
                chunks, lens, pt_rows = self._pad_prompt_batch(
                    reqs, bucket, rows)
                starts = np.zeros((chunks.shape[0],), np.int32)
                starts[: len(reqs)] = start_lens
                args = (jnp.asarray(chunks), jnp.asarray(starts),
                        jnp.asarray(lens))
            fn = exe if exe is not None else self._steps["continue"]
            with span("serve.exec.dispatch"):
                logits, k, v = fn(self.params, *args, self.kv.k_pools,
                                  self.kv.v_pools, pt_rows)
            with span("serve.exec.wait"):
                jax.block_until_ready(logits)
            self.kv = self.kv._replace(k_pools=k, v_pools=v)
            self._count_dispatch()
            self.check_sharding_invariants(
                extra=(("continue_logits", logits, self._rep_sh),)
            )
            self.counters.inc("continuation_prefill_tokens", int(lens.sum()))
            self.counters.inc(
                "prefill_bytes_gathered",
                self._continuation_gather_bytes(
                    [int(s) for s in start_lens], int(chunks.shape[1]),
                    len(reqs),
                ),
            )
            with span("serve.exec.readback"):
                first = self.sample(logits)
                return [np.asarray(first[i]) for i in range(len(reqs))]

    def spill(self, req: Request) -> None:
        """Page-granular spill: only the victim's frames leave the device."""
        self.switcher.spill_kv(req.req_id, self.kv.k_pools, self.kv.v_pools)
        # the spill gather (jnp.take over the page axis of a sharded pool
        # slice) must be read-only w.r.t. layout — symmetric with the
        # restore check below, so a kernel-path mesh run cannot drift
        # between a spill and the next dispatch
        self.check_sharding_invariants()

    def restore(self, req: Request, num_tokens: int,
                shared_pages: list[int] | None = None) -> None:
        """Page-granular restore into freshly allocated frames.

        ``shared_pages``: leading frames the scheduler proved are still
        the pinned prefix's (identical bytes, refcount-held) — re-shared
        by the switcher instead of allocated and scattered.

        ``num_tokens`` may be SHORTER than the spilled length (partial
        restore): the switcher scatters only the leading page-aligned
        portion and drops the record's tail, which the scheduler
        re-prefills through the continuation path."""
        # the DataPlane protocol passes the scheduler's requested restore
        # length; the switcher's own record is authoritative — a request
        # beyond it would silently diverge the re-mapped footprint
        assert num_tokens <= self.switcher.spilled_len(req.req_id), (
            f"restore of req {req.req_id}: scheduler asks {num_tokens} "
            f"tokens, switcher spilled only "
            f"{self.switcher.spilled_len(req.req_id)}"
        )
        k, v, _ = self.switcher.restore_kv(
            req.req_id, self.kv.k_pools, self.kv.v_pools,
            shared_prefix_pages=shared_pages, num_tokens=num_tokens,
        )
        self.kv = self.kv._replace(k_pools=k, v_pools=v)
        # the switcher's scatter is layout-oblivious; the pools must come
        # back in the declared mesh layout or every later dispatch pays a
        # full rematerialization
        self.check_sharding_invariants()

    def discard(self, req: Request) -> None:
        """Free a failed request's host-side swap record (never restored)."""
        self.switcher.discard(req.req_id)

    def export_swap(self, req: Request):
        """Detach the victim's portable swap record (host bytes in the
        pool storage dtype — int8 stays narrow) so the router can migrate
        it to another replica's plane."""
        return self.switcher.export_swap(req.req_id)

    def import_swap(self, req: Request, record) -> None:
        """Adopt a swap record exported from another replica's plane; the
        switcher validates the page geometry before anything moves."""
        self.switcher.import_swap(record)

    # ------------------------------------------------------------------
    # sampling
    # ------------------------------------------------------------------

    def sample(self, logits: jax.Array) -> np.ndarray:
        """Host-path sampling (prefill boundaries and the K=1 decode
        collapse path); every call forces one device->host sync.  The
        fused multi-step decode path samples on device instead."""
        self.counters.inc("host_syncs")
        if self.cfg.greedy:
            return np.asarray(jnp.argmax(logits, axis=-1))
        self._rng, key = jax.random.split(self._rng)
        return np.asarray(
            jax.random.categorical(
                key, logits / self.cfg.temperature, axis=-1
            )
        )
