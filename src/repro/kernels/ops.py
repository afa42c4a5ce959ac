"""Public jit'd wrappers for the kernel package.

These handle shape padding to block multiples, block-size selection, and
(for the gather path) the beyond-paper burst-coalescing optimization, so the
rest of the framework never deals with tiling details.  Every wrapper
dispatches to the Pallas kernel (``use_kernel=True``, default) or the pure
jnp oracle (``use_kernel=False`` — the XLA-native path used by dry-runs).

Dispatch contract: ``use_kernel`` is the ONLY thing that routes to the
oracle.  In particular int8 pools (a non-``None`` ``kv_scale``) no longer
force the ref path — the paged-attention kernels take the scale as a
third scalar-prefetch operand and dequantize each K/V tile in VMEM after
its burst lands, so quantized serving keeps the page-streaming bytes win
(and the ``*_sharded`` wrappers thread ``kv_scale`` through their shard
bodies, so it survives the ('kv', 'hd') mesh too).  The paged copies are
dtype-agnostic: they move whatever element type the pool holds, so a
quantized write is the same burst at the narrow itemsize.

The Pallas kernels assume a single device's pool view (scalar-prefetched
page tables index local frames; no partitioning annotations), so they must
not be traced BARE into a computation laid out over a >1-device mesh.  On
a ('kv', 'hd') serve mesh the ``*_sharded`` wrappers below close that gap
with ``shard_map``: each device runs the unmodified single-device kernel
on exactly its local slice of the KV pools, with per-operand specs derived
from ``launch.mesh.kv_partition_axes`` (the same degradation rule as the
executor's committed pool layout, so the shard a kernel sees IS the shard
the executor placed there):

  * pools ``[P, page, Hkv, hd]`` shard ``P(None, None, kv, hd)``;
  * the page table and every scalar-prefetch operand (lens/starts/
    seq_lens) pass through replicated — the satp analogue every shard
    reads coherently, so page-table translation needs no communication;
  * KV-head ('kv') sharding is embarrassingly parallel: paged attention
    runs an independent online softmax per KV head, so each device
    attends its local heads end to end and the outputs merely concatenate
    along Hkv — no cross-shard reduction, no collective;
  * head_dim ('hd') sharding cuts the QK contraction axis, so the paged
    attention bodies ``all_gather`` K/V pool slices to full head_dim
    (tiled, one concat-sized collective per call) and then claim the
    replicated output every shard computed identically.  The paged copies
    never contract: they stay collective-free even under 'hd'.

The sharded serving executor dispatches through a mesh-bound model twin
(``serve.executor._mesh_kernel_model``) that routes the serve-path ops to
these wrappers, so the kernels stay LIVE under a multi-device mesh; the
old jnp ref-path twin survives only as the explicit ``--no-kernels``
escape hatch, counted by ``ref_path_dispatches``.  Single-device callers
(the kernel differential grids, engines without a mesh) keep the plain
kernel paths regardless of how many devices the process can see.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.common import round_up
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.matmul import matmul as _matmul_kernel
from repro.kernels.paged_attention import (
    paged_decode_attention as _paged_attn_kernel,
)
from repro.kernels.paged_prefill_attention import (
    paged_prefill_attention as _paged_prefill_kernel,
)
from repro.kernels.paged_copy import paged_copy as _paged_copy_kernel
from repro.kernels.paged_copy import paged_copy_at as _paged_copy_at_kernel
from repro.kernels.paged_gather import paged_gather as _paged_gather_kernel
from repro.kernels.wkv6 import wkv6 as _wkv6_kernel


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "use_kernel")
)
def matmul(
    x: jax.Array,
    y: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype: jnp.dtype | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """``x @ y`` for arbitrary shapes (pads to MXU-aligned blocks)."""
    if not use_kernel:
        return ref.matmul_ref(x, y, out_dtype)
    m, k = x.shape
    _, n = y.shape
    bm_, bn_, bk_ = min(bm, round_up(m, 8)), min(bn, round_up(n, 128)), min(
        bk, round_up(k, 128)
    )
    mp, np_, kp = round_up(m, bm_), round_up(n, bn_), round_up(k, bk_)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    yp = jnp.pad(y, ((0, kp - k), (0, np_ - n)))
    out = _matmul_kernel(xp, yp, bm=bm_, bn=bn_, bk=bk_, out_dtype=out_dtype)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "scale", "use_kernel")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    scale: float | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Blockwise attention; pads sequence lengths to block multiples.

    Padding is appended at the *end* of both Q and KV.  For causal
    attention padded KV tokens sit above every real query's diagonal, so
    they are masked structurally; padded Q rows are sliced off.
    """
    if not use_kernel:
        return ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    bq_, bk_ = min(bq, round_up(sq, 8)), min(bk, round_up(sk, 128))
    sqp, skp = round_up(sq, bq_), round_up(sk, bk_)
    if not causal and (sqp != sq or skp != sk):
        raise ValueError("non-causal flash requires block-aligned shapes")
    scale = scale if scale is not None else d ** -0.5
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    kp_ = jnp.pad(k, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    # keep the causal diagonal anchored at the *end*: pad Q and KV equally
    out = _flash_kernel(
        qp, kp_, vp, causal=causal, bq=bq_, bk=bk_, scale=scale
    )
    return out[:, :, :sq]


paged_decode_attention = jax.jit(
    lambda q, k_pool, v_pool, page_table, seq_lens, *, page_size,
    scale=None, window=None, use_kernel=True, kv_scale=None: (
        _paged_attn_kernel(
            q, k_pool, v_pool, page_table, seq_lens,
            page_size=page_size, scale=scale, window=window,
            kv_scale=kv_scale,
        )
        if use_kernel
        else ref.paged_decode_attention_ref(
            q, k_pool, v_pool, page_table, seq_lens,
            page_size=page_size, scale=scale, window=window,
            kv_scale=kv_scale,
        )
    ),
    static_argnames=("page_size", "scale", "window", "use_kernel",
                     "kv_scale"),
)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "bq", "use_kernel", "kv_scale"),
)
def paged_prefill_attention(
    q: jax.Array,            # [B, S, Hkv, G, D] chunk queries
    k_pool: jax.Array,       # [P, page, Hkv, D]
    v_pool: jax.Array,       # [P, page, Hkv, D]
    page_table: jax.Array,   # [B, max_pages] int32
    starts: jax.Array,       # [B] int32
    *,
    page_size: int,
    scale: float | None = None,
    bq: int = 32,
    use_kernel: bool = True,
    kv_scale: float | None = None,
) -> jax.Array:
    """Continuation-chunk attention through the page table.

    Kernel path streams KV pages per query block (one translation per
    page-bounded burst, pages above the causal diagonal skipped); the ref
    path gathers the whole logical prefix (the pre-kernel hot path, kept
    as the differential oracle).  int8 pools (``kv_scale``) dequantize
    INSIDE the kernel — the scale rides in the scalar-prefetch plane and
    tiles upcast in VMEM after the burst, so quantization keeps the
    page-streaming bytes win instead of forcing the gather path.
    """
    if use_kernel:
        return _paged_prefill_kernel(
            q, k_pool, v_pool, page_table, starts,
            page_size=page_size, scale=scale, bq=bq, kv_scale=kv_scale,
        )
    return ref.paged_prefill_attention_ref(
        q, k_pool, v_pool, page_table, starts,
        page_size=page_size, scale=scale, kv_scale=kv_scale,
    )


# ---------------------------------------------------------------------------
# paged memory movement
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("page_size", "use_kernel"))
def paged_copy(
    src: jax.Array,
    pool: jax.Array,
    page_table: jax.Array,
    lens: jax.Array,
    *,
    page_size: int,
    use_kernel: bool = True,
) -> jax.Array:
    if use_kernel:
        return _paged_copy_kernel(
            src, pool, page_table, lens, page_size=page_size
        )
    return ref.paged_copy_ref(src, pool, page_table, lens, page_size=page_size)


@functools.partial(jax.jit, static_argnames=("page_size", "use_kernel"))
def paged_copy_at(
    src: jax.Array,
    pool: jax.Array,
    page_table: jax.Array,
    starts: jax.Array,
    lens: jax.Array,
    *,
    page_size: int,
    use_kernel: bool = True,
) -> jax.Array:
    """Burst copy at arbitrary logical start offsets (continuation prefill)."""
    if use_kernel:
        return _paged_copy_at_kernel(
            src, pool, page_table, starts, lens, page_size=page_size
        )
    return ref.paged_copy_at_ref(
        src, pool, page_table, starts, lens, page_size=page_size
    )


@functools.partial(jax.jit, static_argnames=("page_size", "use_kernel"))
def paged_gather(
    pool: jax.Array,
    page_table_row: jax.Array,
    positions: jax.Array,
    *,
    page_size: int,
    use_kernel: bool = True,
) -> jax.Array:
    """Indexed gather, one translation per element (the paper's C2 cost)."""
    if use_kernel:
        return _paged_gather_kernel(
            pool, page_table_row, positions, page_size=page_size
        )
    return ref.paged_gather_ref(
        pool, page_table_row, positions, page_size=page_size
    )


@functools.partial(jax.jit, static_argnames=("page_size",))
def paged_gather_coalesced(
    pool: jax.Array,
    page_table_row: jax.Array,
    positions: jax.Array,
    *,
    page_size: int,
) -> jax.Array:
    """Beyond-paper: sort-coalesced indexed gather (per-PAGE translation).

    AraOS translates indexed accesses per element; sorting the indices first
    turns runs within a page into single bursts — the translation count
    drops from N to the number of *distinct pages touched* at the cost of a
    sort and an unpermute.  `benchmarks/bench_translation.py` quantifies the
    crossover.  Functionally identical to :func:`paged_gather`.
    """
    order = jnp.argsort(positions)
    sorted_pos = positions[order]
    gathered = ref.paged_gather_ref(
        pool, page_table_row, sorted_pos, page_size=page_size
    )
    inverse = jnp.argsort(order)
    return gathered[inverse]


# ---------------------------------------------------------------------------
# shard_map kernel dispatch over a ('kv', 'hd') serve mesh
#
# Natural-layout (4-D pool) entry points: the serve paths keep pools as
# [P, page, Hkv, hd] and K/V activations as [B, S, Hkv, hd] all the way to
# the shard_map boundary, because a (kv, hd)-sharded 4-D pool flattened to
# the kernels' merged [P, page, W=Hkv*hd] layout is NOT expressible as a
# PartitionSpec on W (the per-device slice is strided).  The merge to W
# happens INSIDE the shard body, on the local slice, where it is a plain
# local reshape.
# ---------------------------------------------------------------------------


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-axes check off.

    The bodies below contain Pallas calls (opaque to the checker) and
    claim replicated outputs the checker cannot verify, so the check is
    disabled — correctness of the claimed specs is what the sharded
    differential grids (tests/test_kernels_sharded.py) pin down.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _kv_axes(mesh, num_kv_heads: int, head_dim: int):
    # deferred import: repro.launch.__init__ -> launch.mesh only (light),
    # and only sharded callers ever need it
    from repro.launch.mesh import kv_partition_axes
    return kv_partition_axes(mesh, num_kv_heads, head_dim)


def paged_copy_sharded(
    src: jax.Array,          # [B, S, Hkv, hd]
    pool: jax.Array,         # [P, page, Hkv, hd]
    page_table: jax.Array,   # [B, max_pages] int32
    lens: jax.Array,         # [B] int32
    *,
    page_size: int,
    mesh,
    use_kernel: bool = True,
) -> jax.Array:
    """:func:`paged_copy` with each device bursting into its pool slice.

    Specs: src/pool ``P(None, None, kv, hd)``, page table + lens
    replicated.  A copy never mixes heads or head_dim lanes, so both mesh
    axes are embarrassingly parallel — no collective on any axis.
    """
    if mesh is None or mesh.size == 1:
        b, s, hkv, hd = src.shape
        return paged_copy(
            src.reshape(b, s, hkv * hd),
            pool.reshape(pool.shape[0], page_size, hkv * hd),
            page_table, lens, page_size=page_size, use_kernel=use_kernel,
        ).reshape(pool.shape)
    kv_ax, hd_ax = _kv_axes(mesh, src.shape[2], src.shape[3])
    spec = jax.sharding.PartitionSpec(None, None, kv_ax, hd_ax)
    rep = jax.sharding.PartitionSpec()

    def body(src_l, pool_l, pt, ln):
        b, s, hk, dd = src_l.shape
        out = paged_copy(
            src_l.reshape(b, s, hk * dd),
            pool_l.reshape(pool_l.shape[0], page_size, hk * dd),
            pt, ln, page_size=page_size, use_kernel=use_kernel,
        )
        return out.reshape(pool_l.shape)

    return _shard_map(body, mesh, (spec, spec, rep, rep), spec)(
        src, pool, page_table, lens
    )


def paged_copy_at_sharded(
    src: jax.Array,          # [B, S, Hkv, hd]
    pool: jax.Array,         # [P, page, Hkv, hd]
    page_table: jax.Array,   # [B, max_pages] int32
    starts: jax.Array,       # [B] int32
    lens: jax.Array,         # [B] int32
    *,
    page_size: int,
    mesh,
    use_kernel: bool = True,
) -> jax.Array:
    """:func:`paged_copy_at` over the mesh (same specs as
    :func:`paged_copy_sharded`; offsets live on the replicated scalar
    plane, so the shard bodies burst independently)."""
    if mesh is None or mesh.size == 1:
        b, s, hkv, hd = src.shape
        return paged_copy_at(
            src.reshape(b, s, hkv * hd),
            pool.reshape(pool.shape[0], page_size, hkv * hd),
            page_table, starts, lens,
            page_size=page_size, use_kernel=use_kernel,
        ).reshape(pool.shape)
    kv_ax, hd_ax = _kv_axes(mesh, src.shape[2], src.shape[3])
    spec = jax.sharding.PartitionSpec(None, None, kv_ax, hd_ax)
    rep = jax.sharding.PartitionSpec()

    def body(src_l, pool_l, pt, st, ln):
        b, s, hk, dd = src_l.shape
        out = paged_copy_at(
            src_l.reshape(b, s, hk * dd),
            pool_l.reshape(pool_l.shape[0], page_size, hk * dd),
            pt, st, ln, page_size=page_size, use_kernel=use_kernel,
        )
        return out.reshape(pool_l.shape)

    return _shard_map(body, mesh, (spec, spec, rep, rep, rep), spec)(
        src, pool, page_table, starts, lens
    )


def paged_decode_attention_sharded(
    q: jax.Array,            # [B, Hkv, G, D]
    k_pool: jax.Array,       # [P, page, Hkv, D]
    v_pool: jax.Array,       # [P, page, Hkv, D]
    page_table: jax.Array,   # [B, max_pages] int32
    seq_lens: jax.Array,     # [B] int32
    *,
    page_size: int,
    mesh,
    scale: float | None = None,
    window: int | None = None,
    use_kernel: bool = True,
    kv_scale: float | None = None,
) -> jax.Array:
    """:func:`paged_decode_attention` with per-device local-slice kernels.

    'kv' shards Hkv on q AND the pools — the per-head online softmax makes
    each device's heads fully independent (no collective; outputs
    concatenate along Hkv via the out spec).  'hd' shards only the pools:
    it cuts the QK contraction, so the body all-gathers K/V to full
    head_dim and every 'hd' shard computes the identical (replicated)
    output.  q and the output stay replicated over 'hd'.
    """
    if mesh is None or mesh.size == 1:
        return paged_decode_attention(
            q, k_pool, v_pool, page_table, seq_lens, page_size=page_size,
            scale=scale, window=window, use_kernel=use_kernel,
            kv_scale=kv_scale,
        )
    kv_ax, hd_ax = _kv_axes(mesh, q.shape[1], q.shape[3])
    pool_spec = jax.sharding.PartitionSpec(None, None, kv_ax, hd_ax)
    q_spec = jax.sharding.PartitionSpec(None, kv_ax, None, None)
    rep = jax.sharding.PartitionSpec()
    gather_hd = hd_ax is not None and mesh.shape[hd_ax] > 1

    def body(q_l, kp_l, vp_l, pt, ln):
        if gather_hd:
            kp_l = jax.lax.all_gather(kp_l, hd_ax, axis=-1, tiled=True)
            vp_l = jax.lax.all_gather(vp_l, hd_ax, axis=-1, tiled=True)
        return paged_decode_attention(
            q_l, kp_l, vp_l, pt, ln, page_size=page_size,
            scale=scale, window=window, use_kernel=use_kernel,
            kv_scale=kv_scale,
        )

    return _shard_map(
        body, mesh, (q_spec, pool_spec, pool_spec, rep, rep), q_spec
    )(q, k_pool, v_pool, page_table, seq_lens)


def paged_prefill_attention_sharded(
    q: jax.Array,            # [B, S, Hkv, G, D]
    k_pool: jax.Array,       # [P, page, Hkv, D]
    v_pool: jax.Array,       # [P, page, Hkv, D]
    page_table: jax.Array,   # [B, max_pages] int32
    starts: jax.Array,       # [B] int32
    *,
    page_size: int,
    mesh,
    scale: float | None = None,
    bq: int = 32,
    use_kernel: bool = True,
    kv_scale: float | None = None,
) -> jax.Array:
    """:func:`paged_prefill_attention` over the mesh (same axis roles as
    :func:`paged_decode_attention_sharded`: 'kv' head-parallel with no
    collective, 'hd' all-gathers K/V pool slices to full head_dim and
    claims the replicated output).  The page-streaming win — touching only
    reachable pages per query block — is per (batch row, KV head, query
    block), so it survives sharding untouched."""
    if mesh is None or mesh.size == 1:
        return paged_prefill_attention(
            q, k_pool, v_pool, page_table, starts, page_size=page_size,
            scale=scale, bq=bq, use_kernel=use_kernel, kv_scale=kv_scale,
        )
    kv_ax, hd_ax = _kv_axes(mesh, q.shape[2], q.shape[4])
    pool_spec = jax.sharding.PartitionSpec(None, None, kv_ax, hd_ax)
    q_spec = jax.sharding.PartitionSpec(None, None, kv_ax, None, None)
    rep = jax.sharding.PartitionSpec()
    gather_hd = hd_ax is not None and mesh.shape[hd_ax] > 1

    def body(q_l, kp_l, vp_l, pt, st):
        if gather_hd:
            kp_l = jax.lax.all_gather(kp_l, hd_ax, axis=-1, tiled=True)
            vp_l = jax.lax.all_gather(vp_l, hd_ax, axis=-1, tiled=True)
        return paged_prefill_attention(
            q_l, kp_l, vp_l, pt, st, page_size=page_size,
            scale=scale, bq=bq, use_kernel=use_kernel, kv_scale=kv_scale,
        )

    return _shard_map(
        body, mesh, (q_spec, pool_spec, pool_spec, rep, rep), q_spec
    )(q, k_pool, v_pool, page_table, starts)


def flash_attention_sharded(
    q: jax.Array,            # [B, Hq, S, D]   (Hq = Hkv * G, kv-major)
    k: jax.Array,            # [B, Hkv, S, D]
    v: jax.Array,            # [B, Hkv, S, D]
    *,
    mesh,
    causal: bool = True,
    scale: float | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """:func:`flash_attention` with heads sharded over 'kv'.

    The prefill chunk attention is not paged, but under a mesh it must
    still not trace a bare Pallas call into the GSPMD computation.  Q
    heads are kv-major (``q.reshape(b, s, Hkv, G, d)`` elsewhere), so
    sharding Hq over 'kv' keeps each device's query heads aligned with its
    KV heads — head-parallel, no collective.  D is the contraction axis
    and stays unsharded; every 'hd' shard computes the identical output
    (claimed replicated)."""
    if mesh is None or mesh.size == 1:
        return flash_attention(
            q, k, v, causal=causal, scale=scale, use_kernel=use_kernel
        )
    kv_ax, _ = _kv_axes(mesh, k.shape[1], q.shape[3])
    spec = jax.sharding.PartitionSpec(None, kv_ax, None, None)

    def body(q_l, k_l, v_l):
        return flash_attention(
            q_l, k_l, v_l, causal=causal, scale=scale, use_kernel=use_kernel
        )

    return _shard_map(body, mesh, (spec, spec, spec), spec)(q, k, v)


# ---------------------------------------------------------------------------
# RWKV-6
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("bt", "use_kernel", "matmul_chunks")
)
def wkv6(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    initial_state: jax.Array | None = None,
    *,
    bt: int = 128,
    use_kernel: bool = True,
    matmul_chunks: bool = False,
) -> tuple[jax.Array, jax.Array]:
    bh, t, n = r.shape
    if matmul_chunks and use_kernel and t % 32 == 0:
        # chunk-parallel Pallas kernel: the [C,C,N] intra-chunk tensor and
        # the state never leave VMEM (kernels/wkv6_chunked.py)
        from repro.kernels.wkv6_chunked import wkv6_chunked as _wkv6_ck
        return _wkv6_ck(r, k, v, w, u, initial_state, chunk=32)
    if not use_kernel:
        if matmul_chunks:
            # flash-linear-attention formulation: MXU matmuls, state
            # traffic / chunk (EXPERIMENTS.md §Perf cell C)
            return ref.wkv6_chunked_matmul_ref(
                r, k, v, w, u, initial_state, chunk=min(bt, 32)
            )
        return ref.wkv6_chunked_ref(r, k, v, w, u, initial_state, chunk=bt)
    bt_ = min(bt, t)
    tp = round_up(t, bt_)
    if tp != t:
        # pad with identity steps: w=1 (no decay), k=0 (no update), r=0
        pad = ((0, 0), (0, tp - t), (0, 0))
        r = jnp.pad(r, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        w = jnp.pad(w, pad, constant_values=1.0)
    o, s_fin = _wkv6_kernel(r, k, v, w, u, initial_state, bt=bt_)
    return o[:, :t], s_fin
