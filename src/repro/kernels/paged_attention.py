"""Paged decode attention — the ADDRGEN/MMU analogue (paper C1 + C2).

One decode step: each sequence's new query attends to its KV cache, which
lives in *physical pages* of a shared HBM pool.  The per-sequence page table
and sequence lengths are **scalar-prefetched into SMEM** and consumed by the
BlockSpec index maps: the logical->physical translation of a page happens
strictly *before* the page's data burst is fetched into VMEM — the literal
TPU restatement of Ara2's ADDRGEN requesting a translation from CVA6's MMU
before issuing each page-bounded AXI burst.  One translation per
``page_size``-token burst; zero per-element translation on this unit-stride
path.

Layouts:
  q        [B, Hkv, G, D]         grouped query heads (G = Hq / Hkv)
  k_pool   [L, P, page, Hkv, D]   physical pages (shared pool) of every
  v_pool   [L, P, page, Hkv, D]   layer, read in place at ``layer``; a
                                  4-D pool is a one-layer stack
  page_table [B, max_pages]       int32, INVALID_PAGE (-1) for unmapped
  seq_lens [B]                    int32 tokens currently valid

Grid ``(B, max_pages)`` with an online softmax per KV head over the page
sweep.  Each grid step fetches one ``(page, Hkv, D)`` block, every head
of one page: ``(Hkv, D)`` are the pool's full minor dims, so the block is
the stored layout and needs no merged-W view.  Pages at or beyond a
sequence's length are skipped with ``pl.when`` (no MXU work, no data
burst consumed from VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import should_interpret

_NEG_INF = -1e30


def _paged_attn_kernel(
    layer_ref,         # SMEM [1]  layer of the stacked pools to read
    seq_lens_ref,      # SMEM [B]
    page_table_ref,    # SMEM [B, max_pages]  (prefetched; used by index maps)
    kv_scale_ref,      # SMEM [1] f32 — dequant scale (1.0 when not quantized)
    q_ref,             # VMEM [1, Hkv, G, D]
    k_ref,             # VMEM [1, 1, page, Hkv, D]  (translated burst, all heads)
    v_ref,             # VMEM [1, 1, page, Hkv, D]
    o_ref,             # VMEM [1, Hkv, G, D]
    m_ref, l_ref, acc_ref,   # VMEM [Hkv, G, 1], [Hkv, G, 1], [Hkv, G, D]
    *,
    page_size: int,
    scale: float,
    window: int | None,
    quantized: bool,
):
    del layer_ref, page_table_ref  # translation consumed by the index maps
    b, p = pl.program_id(0), pl.program_id(1)
    seq_len = seq_lens_ref[b]
    # sliding window: only positions in [lo, seq_len) are visible
    lo = jnp.maximum(seq_len - window, 0) if window is not None else 0

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Page p holds tokens [p*page, (p+1)*page); active iff it intersects
    # [lo, seq_len).  Inactive pages issue no MXU work (paper C4's flip
    # side: wasted bursts are never fetched).
    @pl.when((p * page_size < seq_len) & ((p + 1) * page_size > lo))
    def _body():
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], page_size), 1
        )
        visible = (pos < seq_len) & (pos >= lo)
        for h in range(q_ref.shape[1]):           # static loop over Hkv
            q = q_ref[0, h]                           # [G, D]
            k = k_ref[0, 0, :, h] if len(k_ref.shape) == 5 else k_ref[0, 0]
            v = v_ref[0, 0, :, h] if len(v_ref.shape) == 5 else v_ref[0, 0]
            if quantized:
                # The burst arrived as int8 bytes; upcast in VMEM *after*
                # the DMA so HBM traffic stays at the quantized width.
                # Dequantize to the query's compute dtype — same precision
                # as the fp path.
                k = (k.astype(jnp.float32) * kv_scale_ref[0]).astype(q.dtype)
                v = (v.astype(jnp.float32) * kv_scale_ref[0]).astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                 # [G, page]
            s = jnp.where(visible, s, _NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + pexp.sum(axis=-1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                pexp.astype(v.dtype), v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(p == pl.num_programs(1) - 1)
    def _store():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "window", "kv_scale", "interpret"),
)
def paged_decode_attention(
    q: jax.Array,            # [B, Hkv, G, D]
    k_pool: jax.Array,       # [L, P, page, Hkv, D] or [P, page, Hkv, D]
    v_pool: jax.Array,       # same as k_pool (model dtype or int8)
    page_table: jax.Array,   # [B, max_pages] int32
    seq_lens: jax.Array,     # [B] int32
    layer: jax.Array | int = 0,   # layer of a stacked pool to read
    *,
    page_size: int,
    scale: float | None = None,
    window: int | None = None,
    kv_scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """One decode step through the page table. Returns [B, Hkv, G, D].

    The pools are read in their stored layout: a stack ``[L, P, page,
    Hkv, D]`` of every layer's pool, of which ``layer`` (a traced scalar,
    on the scalar-prefetch plane) is attended, so a layer scan that
    carries the stack hands it over whole, with no per-layer slice.  A
    4-D pool is a one-layer stack.

    When ``kv_scale`` is given the pools hold quantized integers; the
    scale rides in the scalar-prefetch plane next to the page table and
    each K/V tile is dequantized (``x * kv_scale``) in VMEM after its
    burst lands — HBM moves the narrow bytes, the MXU sees ``q.dtype``.
    """
    if interpret is None:
        interpret = should_interpret()
    if k_pool.ndim == 4:
        k_pool, v_pool = k_pool[None], v_pool[None]
    b, hkv, g, d = q.shape
    _, _, page, _, _ = k_pool.shape
    assert page == page_size, (page, page_size)
    max_pages = page_table.shape[1]
    scale = scale if scale is not None else d ** -0.5
    # One block is every head of one page: (Hkv, D) are the pool's full
    # minor dims, so the block is the stored layout.  A lone head's size-1
    # dim is stored major of the page, so it is dropped (a bitcast) and the
    # block is (page, D).
    if hkv == 1:
        k_pool = k_pool.reshape(k_pool.shape[:3] + (d,))
        v_pool = v_pool.reshape(v_pool.shape[:3] + (d,))
    kv_block = (1, 1) + k_pool.shape[2:]

    def kv_index(bi, p, layer_ref, seq_lens_ref, page_table_ref, *_):
        del seq_lens_ref
        # THE translation: logical page p of sequence bi -> physical frame.
        # Unmapped entries (-1) clamp to frame 0; the kernel's seq_len guard
        # ensures their data is never used.
        frame = jnp.maximum(page_table_ref[bi, p], 0)
        return (layer_ref[0], frame) + (0,) * (len(kv_block) - 2)

    def q_index(bi, p, *_):
        return (bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, max_pages),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), q_index),
            pl.BlockSpec(kv_block, kv_index),
            pl.BlockSpec(kv_block, kv_index),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_attn_kernel, page_size=page_size, scale=scale,
            window=window, quantized=kv_scale is not None,
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        # the kernel's name in the compiled program and the device trace,
        # which the chip benchmark matches (tests/test_chip_compile.py)
        name="paged_decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      seq_lens.astype(jnp.int32), page_table.astype(jnp.int32),
      jnp.full((1,), 1.0 if kv_scale is None else kv_scale, jnp.float32),
      q, k_pool, v_pool)
