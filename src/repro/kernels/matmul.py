"""MXU-tiled matmul kernel — the paper's central benchmark kernel (§3.1).

AraOS evaluates virtual-memory overhead on matrix multiplication "as an
example of a vector kernel that heavily requires the cooperation of the
scalar core".  This is its TPU restatement: a classic three-level blocked
matmul with

  * grid ``(M/bm, N/bn, K/bk)`` — K innermost so the f32 accumulator tile
    lives in VMEM scratch across the K sweep (the vector-register working
    set of the RVV kernel);
  * ``(bm, bk) x (bk, bn)`` VMEM blocks feeding the 128x128 MXU;
  * accumulation in f32 regardless of input dtype (bf16 in, f32 acc).

The TLB-sweep benchmark replays this kernel's *address stream* (one burst
per page-bounded block row) through the shared-MMU simulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import should_interpret


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref):
    """One (bm, bn) output tile; accumulates over the K grid dimension."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret")
)
def matmul(
    x: jax.Array,
    y: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype: jnp.dtype | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``x @ y`` with explicit VMEM tiling.

    Shapes must be multiples of the block shape (``ops.matmul`` pads).
    """
    if interpret is None:
        interpret = should_interpret()
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, f"contraction mismatch {k} vs {k2}"
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{k})x({k},{n}) not divisible by blocks ({bm},{bn},{bk})"
    )
    out_dtype = out_dtype or x.dtype
    return pl.pallas_call(
        _matmul_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, y)
