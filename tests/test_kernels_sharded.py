"""Sharded kernel differential grids (markers: ``sharded`` + ``kernels``).

The single-device differential suites (tests/test_kernels.py,
tests/test_paged_prefill_attention.py) pin each Pallas kernel to its jnp
oracle.  This file closes the remaining gap for the mesh: the SAME grids
run through the shard_map dispatch wrappers (``kernels.ops.*_sharded``)
over a real >1-device ('kv', 'hd') mesh, asserting the three-way identity

    shard-local kernel output == single-device kernel output == jnp oracle

plus that the outputs come back carrying the wrappers' declared specs
(pools sharded ``P(None, None, kv, hd)``, attention outputs sharded over
'kv' only / replicated over 'hd' — with replication checks off, a wrong
claimed spec would silently corrupt the global view, so the identity
checks here are what makes the claims trustworthy).

Needs >1 XLA device:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m pytest -q -m "sharded and kernels"

With a single visible device every test skips cleanly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.launch.mesh import kv_partition_axes, make_host_serve_mesh
from test_paged_prefill_attention import make_case

pytestmark = [
    pytest.mark.sharded,
    pytest.mark.kernels,
    pytest.mark.skipif(
        jax.device_count() < 2,
        reason="needs >1 XLA device; set "
               "XLA_FLAGS=--xla_force_host_platform_device_count=8",
    ),
]

KEY = jax.random.PRNGKey(3)

# the differential shapes here use hkv=2, d=16, which a forced-8-device
# host factors as a FULL (kv=2, hd=4) mesh — both axes >1, so the
# head-parallel ('kv') AND the all-gather ('hd') paths are exercised
HKV, G, D = 2, 2, 16


@pytest.fixture(scope="module")
def mesh():
    m = make_host_serve_mesh(HKV, D)
    assert m.size > 1  # guaranteed by the skipif: 2 devices -> (1, 2)
    return m


def _decode_case(page_size, lens, *, hkv=HKV, g=G, d=D, seed=0):
    lens = np.asarray(lens, np.int32)
    b = len(lens)
    max_pages = int(max(-(-int(t) // page_size) for t in lens)) + 1
    n_frames = b * max_pages + 2
    key = jax.random.fold_in(KEY, seed)
    ks = jax.random.split(key, 3)
    k_pool = jax.random.normal(ks[0], (n_frames, page_size, hkv, d))
    v_pool = jax.random.normal(ks[1], (n_frames, page_size, hkv, d))
    rng = np.random.default_rng(seed)
    frames = rng.permutation(n_frames)
    table = np.full((b, max_pages), -1, np.int32)
    fi = 0
    for row in range(b):
        need = -(-int(lens[row]) // page_size)
        table[row, :need] = frames[fi: fi + need]
        fi += need
    q = jax.random.normal(ks[2], (b, hkv, g, d))
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(lens)


def _assert_spec(arr, mesh, *spec):
    want = jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec(*spec))
    assert arr.sharding.is_equivalent_to(want, arr.ndim), (
        f"{arr.sharding} != {want}"
    )


class TestShardedPrefillAttentionGrid:
    """tests/test_paged_prefill_attention.py's core sweep, on the mesh."""

    @pytest.mark.parametrize("page_size", [4, 8])
    @pytest.mark.parametrize("chunk", [1, 3, 8, 17])
    @pytest.mark.parametrize("start", [0, 5, 16])
    def test_grid(self, mesh, page_size, chunk, start):
        q, kp, vp, tab, starts, bq = make_case(
            page_size, [start], [chunk], hkv=HKV, g=G, d=D,
            seed=page_size * 100 + chunk)
        out_sh = ops.paged_prefill_attention_sharded(
            q, kp, vp, tab, starts, page_size=page_size, bq=bq, mesh=mesh)
        out_k = ops.paged_prefill_attention(
            q, kp, vp, tab, starts, page_size=page_size, bq=bq,
            use_kernel=True)
        out_r = ops.paged_prefill_attention(
            q, kp, vp, tab, starts, page_size=page_size, use_kernel=False)
        kv_ax, _ = kv_partition_axes(mesh, HKV, D)
        _assert_spec(out_sh, mesh, None, None, kv_ax, None, None)
        np.testing.assert_allclose(
            np.asarray(out_sh)[0, :chunk], np.asarray(out_k)[0, :chunk],
            rtol=2e-5, atol=2e-5, err_msg="sharded != single-device kernel")
        np.testing.assert_allclose(
            np.asarray(out_sh)[0, :chunk], np.asarray(out_r)[0, :chunk],
            rtol=2e-5, atol=2e-5, err_msg="sharded != jnp oracle")

    def test_batched_rows_mixed_offsets(self, mesh):
        chunks = [10, 7, 1]
        q, kp, vp, tab, starts, bq = make_case(
            8, [5, 0, 13], chunks, hkv=HKV, g=G, d=D, seed=11)
        out_sh = ops.paged_prefill_attention_sharded(
            q, kp, vp, tab, starts, page_size=8, bq=bq, mesh=mesh)
        out_r = ops.paged_prefill_attention(
            q, kp, vp, tab, starts, page_size=8, use_kernel=False)
        for row, chunk in enumerate(chunks):
            np.testing.assert_allclose(
                np.asarray(out_sh)[row, :chunk],
                np.asarray(out_r)[row, :chunk], rtol=2e-5, atol=2e-5,
                err_msg=f"row {row} diverged on the mesh")


class TestShardedDecodeAttention:
    @pytest.mark.parametrize("lens", [[9, 6], [1, 32, 17], [2, 5]])
    def test_vs_single_device_and_oracle(self, mesh, lens):
        q, kp, vp, tab, sl = _decode_case(4, lens, seed=sum(lens))
        out_sh = ops.paged_decode_attention_sharded(
            q, kp, vp, tab, sl, page_size=4, mesh=mesh)
        out_k = ops.paged_decode_attention(
            q, kp, vp, tab, sl, page_size=4, use_kernel=True)
        out_r = ops.paged_decode_attention(
            q, kp, vp, tab, sl, page_size=4, use_kernel=False)
        kv_ax, _ = kv_partition_axes(mesh, HKV, D)
        _assert_spec(out_sh, mesh, None, kv_ax, None, None)
        np.testing.assert_allclose(out_sh, out_k, rtol=2e-5, atol=2e-5,
                                   err_msg="sharded != single-device kernel")
        np.testing.assert_allclose(out_sh, out_r, rtol=2e-5, atol=2e-5,
                                   err_msg="sharded != jnp oracle")


class TestShardedStackedDecodeAttention:
    """The stacked ``[L, P, page, Hkv, D]`` call through the mesh: a 'kv'
    split reads the local stack in place, an 'hd' split slices the layer
    before its all-gather."""

    @pytest.mark.parametrize("split", ["kv", "hd"])
    def test_stack_vs_layer_slice(self, split):
        shape = (2, 1) if split == "kv" else (1, 2)
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()[:2]).reshape(shape), ("kv", "hd"))
        layers = 3
        q, kp, vp, tab, sl = _decode_case(4, [9, 6, 17], seed=layers)
        kps = jnp.stack([kp * (li + 1) for li in range(layers)])
        vps = jnp.stack([vp - li for li in range(layers)])
        at = jax.jit(lambda li: ops.paged_decode_attention_sharded(
            q, kps, vps, tab, sl, li, page_size=4, mesh=mesh))
        kv_ax, _ = kv_partition_axes(mesh, HKV, D)
        for li in range(layers):
            out_sh = at(jnp.int32(li))
            out_k = ops.paged_decode_attention(
                q, kps[li], vps[li], tab, sl, page_size=4, use_kernel=True)
            out_r = ops.paged_decode_attention(
                q, kps, vps, tab, sl, li, page_size=4, use_kernel=False)
            _assert_spec(out_sh, mesh, None, kv_ax, None, None)
            np.testing.assert_allclose(
                out_sh, out_k, rtol=2e-5, atol=2e-5,
                err_msg=f"layer {li}: sharded != single-device kernel")
            np.testing.assert_allclose(
                out_sh, out_r, rtol=2e-5, atol=2e-5,
                err_msg=f"layer {li}: sharded != jnp oracle")


class TestShardedPagedCopies:
    """tests/test_kernels.py's copy grids through the 4-D sharded entry
    points (the merged-W reshape happens inside the shard bodies)."""

    def _copy_case(self, page_size, covers, *, lens=None, s=None, seed=0):
        # ``covers`` sizes the page table (last token each row may touch);
        # ``lens`` is what the op sees; ``s`` is the padded src length.
        covers = np.asarray(covers, np.int32)
        b = len(covers)
        lens = covers if lens is None else np.asarray(lens, np.int32)
        s = s if s is not None else -(-int(covers.max()) // page_size) * page_size
        max_pages = -(-int(covers.max()) // page_size)
        n_frames = b * max_pages + 3
        key = jax.random.fold_in(KEY, 100 + seed)
        ks = jax.random.split(key, 2)
        src = jax.random.normal(ks[0], (b, s, HKV, D))
        pool = jax.random.normal(ks[1], (n_frames, page_size, HKV, D))
        rng = np.random.default_rng(seed)
        frames = rng.permutation(n_frames)
        table = np.full((b, max_pages), -1, np.int32)
        fi = 0
        for row in range(b):
            table[row] = frames[fi: fi + max_pages]
            fi += max_pages
        return src, pool, jnp.asarray(table), jnp.asarray(lens)

    @pytest.mark.parametrize("lens", [[7, 5], [16, 1], [4]])
    def test_paged_copy(self, mesh, lens):
        page = 4
        src, pool, tab, ln = self._copy_case(page, lens, seed=sum(lens))
        out_sh = ops.paged_copy_sharded(
            src, pool, tab, ln, page_size=page, mesh=mesh)
        b, s, hkv, d = src.shape
        out_k = ops.paged_copy(
            src.reshape(b, s, hkv * d),
            pool.reshape(-1, page, hkv * d), tab, ln, page_size=page,
        ).reshape(pool.shape)
        out_r = ops.paged_copy(
            src.reshape(b, s, hkv * d),
            pool.reshape(-1, page, hkv * d), tab, ln, page_size=page,
            use_kernel=False,
        ).reshape(pool.shape)
        kv_ax, hd_ax = kv_partition_axes(mesh, HKV, D)
        _assert_spec(out_sh, mesh, None, None, kv_ax, hd_ax)
        np.testing.assert_array_equal(np.asarray(out_sh), np.asarray(out_k))
        np.testing.assert_array_equal(np.asarray(out_sh), np.asarray(out_r))

    @pytest.mark.parametrize("windows", [[(2, 5), (0, 3)], [(13, 3)]])
    def test_paged_copy_at(self, mesh, windows):
        page = 4
        starts = np.asarray([w[0] for w in windows], np.int32)
        lens = np.asarray([w[1] for w in windows], np.int32)
        smax = int(lens.max())
        src, pool, tab, _ = self._copy_case(
            page, list(starts + lens), lens=lens, s=smax,
            seed=int(lens.sum()))
        st, ln = jnp.asarray(starts), jnp.asarray(lens)
        out_sh = ops.paged_copy_at_sharded(
            src, pool, tab, st, ln, page_size=page, mesh=mesh)
        b, s, hkv, d = src.shape
        out_k = ops.paged_copy_at(
            src.reshape(b, s, hkv * d),
            pool.reshape(-1, page, hkv * d), tab, st, ln, page_size=page,
        ).reshape(pool.shape)
        out_r = ops.paged_copy_at(
            src.reshape(b, s, hkv * d),
            pool.reshape(-1, page, hkv * d), tab, st, ln, page_size=page,
            use_kernel=False,
        ).reshape(pool.shape)
        np.testing.assert_array_equal(np.asarray(out_sh), np.asarray(out_k))
        np.testing.assert_array_equal(np.asarray(out_sh), np.asarray(out_r))


class TestSpecDegradation:
    """Dims that do not divide the mesh must degrade to replicated —
    mirroring ``executor_state_shardings`` exactly — and still match."""

    def test_indivisible_heads_replicate_kv(self, mesh):
        # hkv=3 never divides a kv extent > 1 on this mesh
        q, kp, vp, tab, starts, bq = make_case(
            4, [2], [6], hkv=3, g=2, d=D * mesh.shape["hd"], seed=5)
        out_sh = ops.paged_prefill_attention_sharded(
            q, kp, vp, tab, starts, page_size=4, bq=bq, mesh=mesh)
        out_r = ops.paged_prefill_attention(
            q, kp, vp, tab, starts, page_size=4, use_kernel=False)
        np.testing.assert_allclose(
            np.asarray(out_sh)[0, :6], np.asarray(out_r)[0, :6],
            rtol=2e-5, atol=2e-5)

    def test_indivisible_head_dim_replicates_hd(self, mesh):
        # d=10 does not divide hd extents > 1 from (2,4)/(1,2) meshes
        q, kp, vp, tab, sl = _decode_case(4, [9, 6], d=10, seed=7)
        out_sh = ops.paged_decode_attention_sharded(
            q, kp, vp, tab, sl, page_size=4, mesh=mesh)
        out_r = ops.paged_decode_attention(
            q, kp, vp, tab, sl, page_size=4, use_kernel=False)
        np.testing.assert_allclose(out_sh, out_r, rtol=2e-5, atol=2e-5)


class TestShardedFlashAttention:
    def test_vs_single_device_kernel(self, mesh):
        b, s = 2, 24
        ks = jax.random.split(jax.random.fold_in(KEY, 9), 3)
        q = jax.random.normal(ks[0], (b, HKV * G, s, D))
        k = jax.random.normal(ks[1], (b, HKV, s, D))
        v = jax.random.normal(ks[2], (b, HKV, s, D))
        out_sh = ops.flash_attention_sharded(q, k, v, causal=True,
                                             mesh=mesh)
        out_k = ops.flash_attention(q, k, v, causal=True)
        kv_ax, _ = kv_partition_axes(mesh, HKV, D)
        _assert_spec(out_sh, mesh, None, kv_ax, None, None)
        np.testing.assert_allclose(out_sh, out_k, rtol=2e-5, atol=2e-5)
