"""RWKV-6 (Finch) time-mix recurrence kernel.

Per head of size N, with receptance r_t, key k_t, data-dependent decay w_t
(all [N]), value v_t [N] and bonus u [N]:

    o_t = r_t^T · (diag(u) · k_t v_t^T + S_{t-1})
    S_t = diag(w_t) · S_{t-1} + k_t v_t^T

The [N, N] state S is the vector-register working set: it lives in VMEM
scratch and is carried across the sequential time-block grid dimension —
the recurrence never round-trips to HBM.  Grid ``(B*H, T/bt)``; inside a
block a ``fori_loop`` steps through time (each step is rank-1 update +
matvec, VPU-friendly at N=64).

This is the sub-quadratic serving path for the rwkv6-7b architecture: decode
state is O(N^2) per head regardless of context length (the ``long_500k``
shape runs through it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import should_interpret


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                 o_ref, s_out_ref, s_ref, *, bt: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = s0_ref[0].astype(s_ref.dtype)

    u = u_ref[0]  # [N]

    def step(t, _):
        r = r_ref[0, t]        # [N]
        k = k_ref[0, t]
        v = v_ref[0, t]
        w = w_ref[0, t]
        s = s_ref[...]         # [N, N]
        kv = k[:, None] * v[None, :]              # rank-1 update [N, N]
        o = (r[:, None] * (u[:, None] * kv + s)).sum(axis=0)  # [N]
        o_ref[0, t] = o.astype(o_ref.dtype)
        s_ref[...] = w[:, None] * s + kv
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _store_state():
        s_out_ref[0] = s_ref[...].astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def wkv6(
    r: jax.Array,   # [BH, T, N]
    k: jax.Array,   # [BH, T, N]
    v: jax.Array,   # [BH, T, N]
    w: jax.Array,   # [BH, T, N]  decay in (0, 1), data-dependent
    u: jax.Array,   # [BH, N]     per-head bonus
    initial_state: jax.Array | None = None,  # [BH, N, N] f32
    *,
    bt: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """RWKV-6 recurrence. Returns (o [BH, T, N], final_state [BH, N, N]).

    Supplying ``initial_state`` enables chunked prefill and stateful decode:
    the recurrence continues exactly where the previous chunk stopped.
    """
    if interpret is None:
        interpret = should_interpret()
    bh, t, n = r.shape
    assert t % bt == 0, (t, bt)
    if initial_state is None:
        initial_state = jnp.zeros((bh, n, n), jnp.float32)
    o, s_fin = pl.pallas_call(
        functools.partial(_wkv6_kernel, bt=bt),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, n), r.dtype),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ),
        grid=(bh, t // bt),
        in_specs=[
            pl.BlockSpec((1, bt, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bt, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bt, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bt, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, n), lambda b, i: (b, 0)),
            pl.BlockSpec((1, n, n), lambda b, i: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bt, n), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, n, n), lambda b, i: (b, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, w, u, initial_state)
    return o, s_fin
