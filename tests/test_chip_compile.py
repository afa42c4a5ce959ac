"""Compile the serve-path kernels for a described TPU v5e chip.

Nothing runs: each test lowers a Pallas kernel with ``interpret=False`` at
qwen2-7b widths (Hkv 4, G 7, head_dim 128, page 16, W = Hkv*D = 512)
against one chip of a described ``v5e:2x2`` topology and compiles it with
the TPU compiler, which refuses what interpret mode accepts: block shapes
off the (8, 128) tiling, and more VMEM than a kernel may use.  The
executor's prefill and decode steps are compiled whole too, to pin the
program and kernel names that the chip benchmark finds in a device trace,
and to keep the decode steps free of pool-sized copies.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
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
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kv", sorted(DTYPES))
def test_paged_decode_attention_compiles(one_chip, kv):
    """The 4-D pool, and a stack of 7 layers' pools read at a traced
    layer index."""
    kv_scale = 1 / 24 if kv == "int8" else None
    operands = (
        _sds(one_chip, (BATCH, HKV, G, D), jnp.bfloat16),
        _sds(one_chip, (BATCH, MAX_PAGES), jnp.int32),
        _sds(one_chip, (BATCH,), jnp.int32),
    )
    for shape in [(FRAMES, PAGE, HKV, D), (7, FRAMES, PAGE, HKV, D)]:
        pool = _sds(one_chip, shape, DTYPES[kv])
        _compile(
            lambda q, k, v, pt, lens, li: paged_decode_attention(
                q, k, v, pt, lens, li, page_size=PAGE, kv_scale=kv_scale,
                interpret=False),
            operands[0], pool, pool, *operands[1:],
            _sds(one_chip, (), jnp.int32),
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


def _serve_step_operands(one_chip, monkeypatch, *, layers=1, frames=48,
                         kv_dtype="native", mesh=None):
    """A kernel model at qwen2-7b attention widths (``layers`` layers,
    narrow MLP and vocabulary), kernels compiled rather than interpreted,
    with its parameters and KV pool of ``frames`` pages as shapes on the
    described chip (``one_chip``; the model's kernels shard_map over
    ``mesh`` when one is given)."""
    from repro.models import build_model
    from repro.models.config import ModelConfig

    # the kernels pick interpret mode off the TPU backend; compile them
    for mod in (paged_module, flash_module, copy_module):
        monkeypatch.setattr(mod, "should_interpret", lambda: False)
    model = build_model(ModelConfig(
        name="names", family="dense", num_layers=layers, d_model=512,
        num_heads=HKV * G, num_kv_heads=HKV, d_ff=512, vocab_size=1024,
        head_dim=D, qkv_bias=True, param_dtype="bfloat16"),
        remat=False, use_kernels=True, kv_dtype=kv_dtype, kernel_mesh=mesh)
    params = jax.tree_util.tree_map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
    kv = jax.eval_shape(lambda: model.init_kv_state(BATCH, frames, PAGE, 6))
    pool = _sds(one_chip, kv.k_pools.shape, kv.k_pools.dtype)
    return model, params, pool


@pytest.mark.parametrize("step", ["prefill", "decode", "decode_multi"])
def test_serve_steps_keep_their_trace_names(one_chip, monkeypatch, step):
    """The executor's jitted steps, kernels on, at qwen2-7b attention
    widths (one layer, narrow MLP and vocabulary): the compiled program
    keeps the names ``chipbench/reads.py`` and the roofline readers match
    in the device trace, so a rename fails here and not as a silent
    ``null`` per-layer metric on the chip."""
    from repro.serve import executor as ex

    model, params, pool = _serve_step_operands(one_chip, monkeypatch)

    def sds(shape, dtype):
        return _sds(one_chip, shape, dtype)

    if step == "prefill":
        lowered = ex._prefill_step.lower(
            model, params, sds((BATCH, 96), jnp.int32),
            sds((BATCH,), jnp.int32), pool, pool, sds((BATCH, 6), jnp.int32))
        names = ("_prefill_impl", "flash_attention")
    else:
        lowered = _lower_decode(step, model, params, pool, sds)
        names = (f"_{step}_impl", "paged_decode_attention")
    text = lowered.compile().as_text()
    assert text.startswith(f"HloModule jit_{names[0]},")
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " = " in line]
    assert any(line.lstrip().startswith(f"%{names[1]}.")
               for line in kernels), kernels[:3]


def _lower_decode(step, model, params, pool, sds):
    """The executor's single-chip ``decode`` or ``decode_multi`` step,
    lowered on operand shapes made by ``sds(shape, dtype)``."""
    from repro.serve import executor as ex

    rows = sds((BATCH,), jnp.int32)
    table = sds((BATCH, 6), jnp.int32)
    if step == "decode":
        return ex._decode_step.lower(
            model, params, rows, pool, pool, table, rows,
            sds((BATCH,), jnp.bool_))
    return ex._decode_multi_step.lower(
        model, params, rows, pool, pool, table, rows, rows,
        sds((2,), jnp.uint32), 1.0, 2, True)


def _hlo_op(line):
    """(name, result shape, opcode) of one HLO instruction line."""
    name, rest = line.strip().removeprefix("ROOT ").split(" = ", 1)
    if rest.startswith("("):          # tuple shape: up to its closing paren
        depth = 0
        for i, c in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(c, 0)
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:]
    else:
        shape, rest = rest.split(" ", 1)
    return name, shape, rest.strip().split("(", 1)[0]


@pytest.mark.parametrize("kv", sorted(DTYPES))
@pytest.mark.parametrize("step", ["decode", "decode_multi"])
def test_decode_keeps_the_pools_in_place(one_chip, monkeypatch, step, kv):
    """The decode step carries the stacked K/V pools whole from the donated
    input to the output: every layer's new rows are scattered into the
    stack in place and the kernel reads the stack by layer index.  No op
    whose shape holds the pool's frame count may appear besides
    parameters, bitcasts, tuples, the layer loop, the kernel and the
    in-place scatter: no copy, reshape, dynamic-slice or
    dynamic-update-slice of a pool or a stack."""
    frames = 1031                      # a count no other dim shares
    model, params, pool = _serve_step_operands(
        one_chip, monkeypatch, layers=3, frames=frames,
        kv_dtype="int8" if kv == "int8" else "native")
    text = _lower_decode(step, model, params, pool,
                         lambda shape, dtype: _sds(one_chip, shape, dtype)
                         ).compile().as_text()
    _assert_pools_in_place(text, frames)


def test_mesh_decode_keeps_the_pools_in_place(topo, monkeypatch):
    """The serve mesh's decode step, one KV head per chip (kv = 4 over the
    described 2x2): each chip's stack stays in place too; its lone head is
    read as a (page, D) block, a bitcast of the stored layout."""
    from repro.serve import executor as ex

    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(4, 1),
                             ("kv", "hd"))
    # a pool too large for the compiler to stage a chip's share in VMEM
    frames = 8191
    # shapes with no sharding: the mesh jit's in_shardings place them
    model, params, pool = _serve_step_operands(
        None, monkeypatch, layers=3, frames=frames, mesh=mesh)
    rows = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    text = ex._sharded_steps(model, mesh)["decode"].lower(
        params, rows, pool, pool, jax.ShapeDtypeStruct((BATCH, 6), jnp.int32),
        rows, jax.ShapeDtypeStruct((BATCH,), jnp.bool_)).compile().as_text()
    _assert_pools_in_place(text, frames)


def _assert_pools_in_place(text, frames):
    """No op whose shape holds the pool's ``frames`` in the compiled
    ``text`` but parameters, bitcasts, tuples, the layer loop, the decode
    kernel and the K and V scatters (a scatter, or a fusion rooted in
    one), and both pools donated."""
    header = text.splitlines()[0]
    assert header.count("may-alias") >= 2, header[:300]   # both pools donated

    roots, comp, ops = {}, None, []
    for line in text.splitlines()[1:]:
        if line.endswith("{") and " = " not in line:    # a computation
            comp = line.removeprefix("ENTRY ").split(" ", 1)[0]
        elif line.lstrip().startswith(("%", "ROOT %")) and " = " in line:
            ops.append((line, *_hlo_op(line)))
            if line.lstrip().startswith("ROOT "):
                roots[comp] = ops[-1][3]
    allowed = {"parameter", "bitcast", "tuple", "get-tuple-element",
               "while", "scatter"}
    pool_sized = re.compile(rf"[\[,]{frames}[,\]]")
    found = []
    for line, name, shape, op in ops:
        if not pool_sized.search(shape) or op in allowed:
            continue
        if op == "custom-call" and name.startswith("%paged_decode_attention."):
            continue
        if op == "fusion":
            called = re.search(r"calls=(%[\w.\-]+)", line).group(1)
            if roots.get(called) == "scatter":
                continue
        found.append(f"{op}: {line.strip()[:160]}")
    assert not found, found
    # the K and the V scatter
    assert sum(op == "scatter" and bool(pool_sized.search(shape))
               for _, _, shape, op in ops) >= 2


@pytest.mark.parametrize("rows", [1, 4])
def test_prefill_compiles_on_each_rung(one_chip, monkeypatch, rows):
    """The AOT prefill runs on the low rungs of the row ladder
    (``executor.row_buckets``) too: the same program, kernels and names,
    with 1 or 4 rows against the full pool."""
    from repro.serve import executor as ex

    model, params, pool = _serve_step_operands(one_chip, monkeypatch)
    text = ex._prefill_step.lower(
        model, params, _sds(one_chip, (rows, 96), jnp.int32),
        _sds(one_chip, (rows,), jnp.int32), pool, pool,
        _sds(one_chip, (rows, 6), jnp.int32)).compile().as_text()
    assert text.startswith("HloModule jit__prefill_impl,")
    assert any(line.lstrip().startswith("%flash_attention.")
               for line in text.splitlines()
               if "tpu_custom_call" in line and " = " in line)
