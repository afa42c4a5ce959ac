"""Chunk-parallel WKV-6 Pallas kernel (flash-linear-attention formulation).

The §Perf cell-C analysis (EXPERIMENTS.md) showed the XLA-native
chunked-matmul WKV is still memory-bound: the per-chunk decay tensor
``exp(cum_i - cum_j)`` ([C, C, N]) and the running state round-trip HBM every
chunk.  This kernel is the TPU-native fix: grid ``(BH, T/C)`` with the
``[N, N]`` state AND all chunk-local tensors resident in VMEM scratch — HBM
traffic collapses to the r/k/v/w/o streams.

Math per chunk (all in f32, exponents <= 0 by construction):
    cum       = cumsum(log w)              (inclusive)
    a_in[i]   = exp(cum[i-1])              (decay from chunk start, excl.)
    o_i       = (r_i * a_in[i]) @ S
              + sum_{j<i} (r_i . k_j) exp(cum[i-1] - cum[j]) v_j
              + (r_i . k_i * u) v_i
    S         = exp(cum[C-1]) * S + sum_j exp(cum[C-1] - cum[j]) k_j v_j^T

Validated in interpret mode against ``ref.wkv6_ref`` (tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import should_interpret


def _wkv6_chunked_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                         o_ref, s_out_ref, s_ref, *, chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = s0_ref[0].astype(s_ref.dtype)

    rr = r_ref[0].astype(jnp.float32)          # [C, N]
    kk = k_ref[0].astype(jnp.float32)
    vv = v_ref[0].astype(jnp.float32)
    ww = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)           # [N]
    s = s_ref[...]                             # [N, N]

    logw = jnp.log(jnp.maximum(ww, 1e-38))
    cum = jnp.cumsum(logw, axis=0)             # [C, N] inclusive
    cum_excl = cum - logw
    a_in = jnp.exp(cum_excl)                   # [C, N]

    # inter-chunk: (r * a_in) @ S                     -> [C, N]
    o = jax.lax.dot_general(
        rr * a_in, s, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # intra-chunk: att[i, j] = sum_n r_i k_j exp(cum_excl_i - cum_j), j < i
    delta = cum_excl[:, None, :] - cum[None, :, :]        # [C, C, N]
    mask = (jnp.arange(chunk)[:, None] > jnp.arange(chunk)[None, :])
    delta = jnp.where(mask[:, :, None], delta, -jnp.inf)
    att = jnp.einsum("in,jn,ijn->ij", rr, kk, jnp.exp(delta))
    o = o + jax.lax.dot_general(
        att, vv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # diagonal bonus
    o = o + (rr * u[None, :] * kk).sum(axis=1, keepdims=True) * vv
    o_ref[0] = o.astype(o_ref.dtype)

    # state update
    d_c = jnp.exp(cum[-1, :])                  # [N]
    tail = jnp.exp(cum[-1:, :] - cum)          # [C, N]
    s_ref[...] = d_c[:, None] * s + jax.lax.dot_general(
        (kk * tail), vv, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _store():
        s_out_ref[0] = s_ref[...].astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_chunked(
    r: jax.Array,   # [BH, T, N]
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,   # [BH, N]
    initial_state: jax.Array | None = None,  # [BH, N, N] f32
    *,
    chunk: int = 32,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Chunk-parallel WKV-6. Returns (o [BH, T, N], final_state)."""
    if interpret is None:
        interpret = should_interpret()
    bh, t, n = r.shape
    assert t % chunk == 0, (t, chunk)
    if initial_state is None:
        initial_state = jnp.zeros((bh, n, n), jnp.float32)
    return pl.pallas_call(
        functools.partial(_wkv6_chunked_kernel, chunk=chunk),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, n), r.dtype),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ),
        grid=(bh, t // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, n), lambda b, i: (b, 0)),
            pl.BlockSpec((1, n, n), lambda b, i: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, n, n), lambda b, i: (b, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, w, u, initial_state)
