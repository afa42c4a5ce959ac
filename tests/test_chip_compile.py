"""Compile the serve-path kernels for a described TPU v5e chip.

Nothing runs: each test lowers a Pallas kernel with ``interpret=False`` at
qwen2-7b widths (Hkv 4, G 7, head_dim 128, page 16, W = Hkv*D = 512)
against one chip of a described ``v5e:2x2`` topology and compiles it with
the TPU compiler, which refuses what interpret mode accepts: block shapes
off the (8, 128) tiling, and more VMEM than a kernel may use.  The
executor's prefill and decode steps are compiled whole too, to pin the
program and kernel names that the chip benchmark finds in a device trace.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import flash_attention as flash_module
from repro.kernels import paged_attention as paged_module
from repro.kernels import paged_copy as copy_module
from repro.kernels.common import round_up
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.paged_copy import paged_copy, paged_copy_at
from repro.kernels.paged_prefill_attention import paged_prefill_attention

HKV, G, D, PAGE = 4, 7, 128, 16
W = HKV * D
FRAMES, MAX_PAGES, BATCH = 4096, 64, 8
DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kv", sorted(DTYPES))
def test_paged_decode_attention_compiles(one_chip, kv):
    kv_scale = 1 / 24 if kv == "int8" else None
    pool = _sds(one_chip, (FRAMES, PAGE, HKV, D), DTYPES[kv])
    _compile(
        lambda q, k, v, pt, lens: paged_decode_attention(
            q, k, v, pt, lens, page_size=PAGE, kv_scale=kv_scale,
            interpret=False),
        _sds(one_chip, (BATCH, HKV, G, D), jnp.bfloat16), pool, pool,
        _sds(one_chip, (BATCH, MAX_PAGES), jnp.int32),
        _sds(one_chip, (BATCH,), jnp.int32),
    )


@pytest.mark.parametrize("chunk", [24, 512])
@pytest.mark.parametrize("kv", sorted(DTYPES))
def test_paged_prefill_attention_compiles(one_chip, kv, chunk):
    kv_scale = 1 / 24 if kv == "int8" else None
    pool = _sds(one_chip, (FRAMES, PAGE, HKV, D), DTYPES[kv])
    _compile(
        lambda q, k, v, pt, starts: paged_prefill_attention(
            q, k, v, pt, starts, page_size=PAGE, kv_scale=kv_scale,
            interpret=False),
        _sds(one_chip, (2, chunk, HKV, G, D), jnp.bfloat16), pool, pool,
        _sds(one_chip, (2, MAX_PAGES), jnp.int32),
        _sds(one_chip, (2,), jnp.int32),
    )


@pytest.mark.parametrize("kv", sorted(DTYPES))
def test_paged_copies_compile(one_chip, kv):
    src = _sds(one_chip, (BATCH, 512, W), jnp.bfloat16)
    pool = _sds(one_chip, (FRAMES, PAGE, W), DTYPES[kv])
    pt = _sds(one_chip, (BATCH, MAX_PAGES), jnp.int32)
    lens = _sds(one_chip, (BATCH,), jnp.int32)
    _compile(
        lambda s, p, t, n: paged_copy(s, p, t, n, page_size=PAGE,
                                      interpret=False),
        src, pool, pt, lens,
    )
    _compile(
        lambda s, p, t, st, n: paged_copy_at(s, p, t, st, n, page_size=PAGE,
                                             interpret=False),
        src, pool, pt, lens, lens,
    )


@pytest.mark.parametrize("seq", [24, 128, 200])
def test_flash_attention_compiles(one_chip, seq):
    # the block choice and padding of kernels.ops.flash_attention
    bq, bk = min(128, round_up(seq, 8)), min(128, round_up(seq, 128))
    sq, sk = round_up(seq, bq), round_up(seq, bk)
    _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True, bq=bq, bk=bk,
                                        interpret=False),
        _sds(one_chip, (1, HKV * G, sq, D), jnp.bfloat16),
        _sds(one_chip, (1, HKV, sk, D), jnp.bfloat16),
        _sds(one_chip, (1, HKV, sk, D), jnp.bfloat16),
    )


@pytest.mark.parametrize("step", ["prefill", "decode", "decode_multi"])
def test_serve_steps_keep_their_trace_names(one_chip, monkeypatch, step):
    """The executor's jitted steps, kernels on, at qwen2-7b attention
    widths (one layer, narrow MLP and vocabulary): the compiled program
    keeps the names ``chipbench/reads.py`` and the roofline readers match
    in the device trace, so a rename fails here and not as a silent
    ``null`` per-layer metric on the chip."""
    from repro.models import build_model
    from repro.models.config import ModelConfig
    from repro.serve import executor as ex

    # the kernels pick interpret mode off the TPU backend; compile them
    for mod in (paged_module, flash_module, copy_module):
        monkeypatch.setattr(mod, "should_interpret", lambda: False)
    model = build_model(ModelConfig(
        name="names", family="dense", num_layers=1, d_model=512,
        num_heads=HKV * G, num_kv_heads=HKV, d_ff=512, vocab_size=1024,
        head_dim=D, qkv_bias=True, param_dtype="bfloat16"),
        remat=False, use_kernels=True)

    def sds(shape, dtype):
        return _sds(one_chip, shape, dtype)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
    kv = jax.eval_shape(lambda: model.init_kv_state(BATCH, 48, PAGE, 6))
    pool = sds(kv.k_pools.shape, kv.k_pools.dtype)
    rows = sds((BATCH,), jnp.int32)
    table = sds((BATCH, 6), jnp.int32)
    if step == "prefill":
        lowered = ex._prefill_step.lower(
            model, params, sds((BATCH, 96), jnp.int32), rows, pool, pool,
            table)
        names = ("_prefill_impl", "flash_attention")
    elif step == "decode":
        lowered = ex._decode_step.lower(
            model, params, rows, pool, pool, table, rows,
            sds((BATCH,), jnp.bool_))
        names = ("_decode_impl", "paged_decode_attention")
    else:
        lowered = ex._decode_multi_step.lower(
            model, params, rows, pool, pool, table, rows, rows,
            sds((2,), jnp.uint32), 1.0, 2, True)
        names = ("_decode_multi_impl", "paged_decode_attention")
    text = lowered.compile().as_text()
    assert text.startswith(f"HloModule jit_{names[0]},")
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " = " in line]
    assert any(line.lstrip().startswith(f"%{names[1]}.")
               for line in kernels), kernels[:3]
