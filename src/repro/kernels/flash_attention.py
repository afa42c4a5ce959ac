"""Blockwise (flash) attention kernel for long prefill.

32 k-token prefill cannot materialize S = Q K^T (32k x 32k f32 = 4 GiB per
head), so attention is computed blockwise with an online softmax: grid
``(batch*q_heads, Sq/bq, Sk/bk)``, running max ``m``, normalizer ``l`` and
accumulator held in VMEM scratch across the KV sweep.

GQA is handled in the index maps: query head ``h`` reads KV head
``h // group`` — no KV replication in HBM (the bandwidth saving is the whole
point of GQA).  Causal masking compares absolute token indices derived from
the block ids; fully-masked KV blocks are skipped via ``pl.when`` (no MXU
work issued), which matters: at 32 k, half the blocks are dead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import should_interpret

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, bq: int, bk: int, scale: float, causal: bool):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        q = q_ref[0]                                  # [bq, d]
        k = k_ref[0]                                  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # [bq, bk]
        if causal:
            q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[...]                           # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                        # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)               # [bq, 1]
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Skip KV blocks strictly above the diagonal: no MXU work issued.
        pl.when(ik * bk <= iq * bq + (bq - 1))(_body)
    else:
        _body()

    @pl.when(ik == pl.num_programs(2) - 1)
    def _store():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "bq", "bk", "scale", "interpret"),
)
def flash_attention(
    q: jax.Array,                 # [B, Hq, Sq, D]
    k: jax.Array,                 # [B, Hkv, Sk, D]
    v: jax.Array,                 # [B, Hkv, Sk, D]
    *,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Blockwise attention with online softmax. Returns [B, Hq, Sq, D]."""
    if interpret is None:
        interpret = should_interpret()
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, f"GQA requires Hq % Hkv == 0 ({hq}, {hkv})"
    group = hq // hkv
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    scale = scale if scale is not None else d ** -0.5

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)

    def kv_index(bh, iq, ik):
        # query head bh = bi*hq + h  ->  kv row bi*hkv + h // group
        return ((bh // hq) * hkv + (bh % hq) // group, ik, 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, bq=bq, bk=bk, scale=scale, causal=causal
        ),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        grid=(b * hq, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running normalizer
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the kernel's name in the compiled program and the device trace,
        # which the chip benchmark matches (tests/test_chip_compile.py)
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)
