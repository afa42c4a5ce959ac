"""Chunked-prefill paged attention — streaming KV pages per query block.

Continuation prefill extends a sequence that already holds ``start`` tokens
in the paged KV cache by a chunk of new tokens.  The gathered-pages jnp path
(kept as the oracle in :func:`repro.kernels.ref.paged_prefill_attention_ref`)
materializes the *whole* logical prefix — ``max_pages x page_size`` tokens —
per layer per chunk, the software equivalent of taking a TLB miss on every
page regardless of how much of the table is live.  This kernel instead
streams exactly the pages each query block can see, translating each page
through the scalar-prefetched page table immediately before its burst is
fetched — Ara2's ADDRGEN/MMU handshake (one translation per page-bounded
burst), applied to the chunked-prefill hot path.

Grid / blocking scheme
======================
::

    grid = (B, Hkv, S*G // bs, max_pages)           # pages innermost

  * axis 0 — batch row (one forked/continued request per row; same-step
    forked admissions run as ONE batched call, B > 1);
  * axis 1 — KV head; query heads of the same GQA group share the sweep;
  * axis 2 — query block: the chunk's queries, flattened to ``S*G`` rows
    (token-major, group-minor) and tiled ``bs = bq * G`` rows per block so
    one block is ``bq`` whole query tokens;
  * axis 3 — the KV page sweep.  Logical page ``p`` of row ``b`` is
    translated to a physical frame by the BlockSpec index map *reading the
    prefetched page table from SMEM*; the online softmax (running max /
    normalizer / accumulator in VMEM scratch) makes the sweep single-pass.

Pages strictly above the block's causal diagonal — ``p * page_size >
start_b + last_token(block)`` — are skipped twice over: ``pl.when`` elides
their MXU work, and the KV index map clamps their page index to the last
causally reachable page, so consecutive grid steps name the same block and
Pallas elides the DMA (no data burst consumed).  For a continuation chunk
at offset ``start`` this bounds the pages fetched by ``pages(start +
chunk_padded)`` instead of ``max_pages`` (``pages_touched`` is the exact
model), and trailing-block savings grow with the table headroom.

Semantics match the gathered-pages oracle exactly: causal masking on
absolute logical positions (``k_pos <= start_b + q_idx``) across the
page/offset boundary, unmapped page-table entries clamped to frame 0 (their
keys are either causally masked or belong to don't-care padded query rows —
identical don't-care reads to the oracle's ``max(table, 0)`` gather).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, should_interpret

_NEG_INF = -1e30


def _paged_prefill_kernel(
    starts_ref,        # SMEM [B] — tokens already cached per sequence
    page_table_ref,    # SMEM [B, max_pages] (prefetched; used by index maps)
    kv_scale_ref,      # SMEM [1] f32 — dequant scale (1.0 when not quantized)
    q_ref,             # VMEM [1, 1, bs, D]  (bs = bq * G flattened rows)
    k_ref,             # VMEM [1, page, D]  (translated burst, one head)
    v_ref,             # VMEM [1, page, D]
    o_ref,             # VMEM [1, 1, bs, D]
    m_ref, l_ref, acc_ref,
    *,
    page_size: int,
    bq: int,
    group: int,
    scale: float,
    quantized: bool,
):
    del page_table_ref  # translation consumed by the index maps
    b, qb, p = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    start = starts_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Last absolute position any query row of this block occupies; pages
    # starting beyond it are entirely above the causal diagonal.
    last_q_pos = start + (qb + 1) * bq - 1

    @pl.when(p * page_size <= last_q_pos)
    def _body():
        q = q_ref[0, 0]                               # [bs, D]
        k = k_ref[0]                                  # [page, D]
        v = v_ref[0]                                  # [page, D]
        if quantized:
            # int8 burst → upcast in VMEM after the DMA; HBM traffic is
            # the quantized bytes, the MXU computes in the query's dtype.
            k = (k.astype(jnp.float32) * kv_scale_ref[0]).astype(q.dtype)
            v = (v.astype(jnp.float32) * kv_scale_ref[0]).astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # [bs, page]
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = start + qb * bq + row // group        # absolute q position
        k_pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp.astype(v.dtype), v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(p == pl.num_programs(3) - 1)
    def _store():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def last_reachable_page(start, qb, *, page_size: int, bq: int):
    """Last KV page query block ``qb`` can causally reach (padded block
    end).  THE shared clamp formula: the kernel's ``kv_index`` map uses it
    on traced scalars, ``pages_touched`` on Python ints — one source of
    truth, so the analytical bytes model cannot desync from what the
    kernel actually fetches."""
    return (start + (qb + 1) * bq - 1) // page_size


def pages_touched(start: int, chunk: int, max_pages: int, *,
                  page_size: int, bq: int) -> int:
    """Pages the kernel fetches for one (start, chunk) row — the analytical
    bytes-gathered model used by ``benchmarks/bench_prefill_continue.py``
    (the gathered-pages oracle always touches ``max_pages``, once per query
    chunk, independent of ``start + chunk``).

    Exact by construction: the per-block fetch count is
    ``last_reachable_page(...) + 1`` capped at the table — the same
    formula the kernel's index map clamps with (the clamp makes Pallas
    elide the DMA for every page beyond it)."""
    if not chunk:
        return 0
    bq = max(1, min(bq, chunk))
    total = 0
    for qb in range(cdiv(chunk, bq)):
        last = last_reachable_page(start, qb, page_size=page_size, bq=bq)
        total += min(last + 1, max_pages)
    return total


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "bq", "kv_scale", "interpret"),
)
def paged_prefill_attention(
    q: jax.Array,            # [B, S, Hkv, G, D] chunk queries
    k_pool: jax.Array,       # [P, page, Hkv, D]  (model dtype or int8)
    v_pool: jax.Array,       # [P, page, Hkv, D]
    page_table: jax.Array,   # [B, max_pages] int32
    starts: jax.Array,       # [B] int32 — tokens already cached per row
    *,
    page_size: int,
    scale: float | None = None,
    bq: int = 32,
    kv_scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Chunked-prefill attention through the page table.

    Query token ``t`` of row ``b`` sits at absolute position
    ``starts[b] + t`` and attends causally over logical positions
    ``[0, starts[b] + t]`` — cache plus committed chunk prefix (the chunk's
    own KV must already be written through the table, see
    ``ops.paged_copy_at``).  When ``kv_scale`` is given the pools hold
    quantized integers; the scale is scalar-prefetched next to the page
    table and tiles are dequantized in VMEM after each burst lands.
    Returns [B, S, Hkv, G, D].
    """
    if interpret is None:
        interpret = should_interpret()
    b, s, hkv, g, d = q.shape
    n_pages, page, _, _ = k_pool.shape
    assert page == page_size, (page, page_size)
    max_pages = page_table.shape[1]
    scale = scale if scale is not None else d ** -0.5
    # merged-W view: head h of a frame is the (page, D) block at lane
    # offset h*D (same reason as the decode kernel's view)
    k_pool = k_pool.reshape(n_pages, page, hkv * d)
    v_pool = v_pool.reshape(n_pages, page, hkv * d)

    bq = max(1, min(bq, s))
    sp = cdiv(s, bq) * bq
    # token-major, group-minor row flattening: [B, Hkv, S*G, D]
    qf = q.transpose(0, 2, 1, 3, 4).reshape(b, hkv, s * g, d)
    if sp != s:
        # padded rows sit beyond the chunk; their outputs are sliced off
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, (sp - s) * g), (0, 0)))
    bs = bq * g

    def kv_index(bi, h, qb, p, starts_ref, page_table_ref, *_):
        # Pages above the block's causal diagonal are clamped to the last
        # reachable page: Pallas elides the DMA when consecutive grid steps
        # name the same block, so skipped pages cost no data burst (the
        # pl.when in the kernel body already skips their compute).
        last_page = last_reachable_page(
            starts_ref[bi], qb, page_size=page_size, bq=bq
        )
        p_eff = jnp.minimum(p, last_page)
        # THE translation: logical page p of row bi -> physical frame.
        # Unmapped entries (-1) clamp to frame 0; causal masking (or the
        # don't-care status of padded rows) keeps their data unused.
        frame = jnp.maximum(page_table_ref[bi, p_eff], 0)
        return (frame, 0, h)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, sp // bq, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, bs, d), lambda bi, h, qb, p, *_: (bi, h, qb, 0)),
            pl.BlockSpec((1, page_size, d), kv_index),
            pl.BlockSpec((1, page_size, d), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bs, d), lambda bi, h, qb, p, *_: (bi, h, qb, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((bs, 1), jnp.float32),   # running max
            pltpu.VMEM((bs, 1), jnp.float32),   # running normalizer
            pltpu.VMEM((bs, d), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_prefill_kernel, page_size=page_size, bq=bq, group=g,
            scale=scale, quantized=kv_scale is not None,
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, sp * g, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(starts.astype(jnp.int32), page_table.astype(jnp.int32),
      jnp.full((1,), 1.0 if kv_scale is None else kv_scale, jnp.float32),
      qf, k_pool, v_pool)
    return out[:, :, : s * g].reshape(b, hkv, s, g, d).transpose(0, 2, 1, 3, 4)
