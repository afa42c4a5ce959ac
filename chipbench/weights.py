"""Random weights of a dense GQA decoder, drawn on the device from a seed.

The benchmark makes the weights itself, in one jitted call, in the dtype
the configuration serves (``torch_dtype``).  The harness hands them to
the program under test; the reference (``reference.py``) draws the same
weights again from the same seed, so it takes nothing the program made.

Layout (leading axis L on every per-layer leaf)::

    embed [V, D]     ln1 [L, D]      wq [L, D, Hq*hd]    bq [L, Hq*hd]
    wk [L, D, Hkv*hd]  bk [L, Hkv*hd]  wv [L, D, Hkv*hd]  bv [L, Hkv*hd]
    wo [L, Hq*hd, D]   ln2 [L, D]      w_gate [L, D, F]   w_up [L, D, F]
    w_down [L, F, D]   ln_f [D]        head [D, V]

Biases exist only where ``attention_bias`` is true.  They, and the norm
scales, are drawn away from their usual zero and one, so that a path that
dropped a bias or a scale would show in the comparison.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def seed_words(seed: int) -> np.ndarray:
    """Two uint32 words of a non-negative seed (seeds exceed 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                      np.uint32)


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape for the model of config file ``cfg``."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    n = cfg["num_hidden_layers"]
    out = {
        "embed": (v, d), "ln1": (n, d),
        "wq": (n, d, hq * hd), "wk": (n, d, hkv * hd), "wv": (n, d, hkv * hd),
        "wo": (n, hq * hd, d), "ln2": (n, d),
        "w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d),
        "ln_f": (d,), "head": (d, v),
    }
    if cfg["attention_bias"]:
        out.update(bq=(n, hq * hd), bk=(n, hkv * hd), bv=(n, hkv * hd))
    return out


def _std(name: str, shape: tuple[int, ...]) -> float:
    if name == "embed":
        return 1.0
    if name in ("bq", "bk", "bv"):
        return 0.5
    if name in ("ln1", "ln2", "ln_f"):
        return 0.1
    return 1.0 / math.sqrt(shape[-2])        # fan-in of a [.., d_in, d_out]


@functools.partial(jax.jit, static_argnums=(0,))
def _draw(spec: tuple, words: jax.Array) -> dict[str, jax.Array]:
    dtype_name, leaves = spec
    dt = DTYPES[dtype_name]
    key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
    keys = jax.random.split(key, len(leaves))
    out = {}
    for k, (name, shape) in zip(keys, leaves):
        x = jax.random.normal(k, shape, dt) * jnp.asarray(_std(name, shape), dt)
        if name in ("ln1", "ln2", "ln_f"):
            x = x + jnp.asarray(1.0, dt)
        out[name] = x
    return out


def make(cfg: dict, seed: int) -> dict[str, jax.Array]:
    """All weights of config file ``cfg`` for ``seed``, on the default
    device, in one jitted call."""
    spec = (cfg["torch_dtype"], tuple(sorted(shapes(cfg).items())))
    return _draw(spec, jnp.asarray(seed_words(seed)))
