"""Per-kernel allclose sweeps against the pure-jnp oracles (ref.py).

Every Pallas kernel is exercised in interpret mode across shape and dtype
sweeps, plus hypothesis property tests on the paged-memory kernels'
translation semantics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # deterministic fallback shim (see requirements-dev.txt)
    from _prop_fallback import given, settings, st

from repro.core import VMemConfig, VirtualMemory
from repro.kernels import ops, ref

pytestmark = pytest.mark.kernels

KEY = jax.random.PRNGKey(42)


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    x = jax.random.normal(key, shape, jnp.float32) * scale
    return x.astype(dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-4, atol=2e-4
    )


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------


class TestMatmul:
    @pytest.mark.parametrize("m,k,n", [
        (128, 128, 128), (256, 384, 128), (128, 512, 256),
        (100, 70, 50), (1, 128, 128), (8, 1024, 8),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, m, k, n, dtype):
        x = rand(jax.random.fold_in(KEY, m * k), (m, k), dtype)
        y = rand(jax.random.fold_in(KEY, k * n + 1), (k, n), dtype)
        out = ops.matmul(x, y, out_dtype=jnp.float32)
        expect = ref.matmul_ref(x, y, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expect), **tol(dtype)
        )

    def test_block_shape_sweep(self):
        x = rand(KEY, (256, 256))
        y = rand(jax.random.fold_in(KEY, 1), (256, 256))
        expect = np.asarray(x @ y)
        for bm, bn, bk in [(64, 64, 64), (128, 256, 64), (256, 128, 256)]:
            out = ops.matmul(x, y, bm=bm, bn=bn, bk=bk)
            np.testing.assert_allclose(np.asarray(out), expect,
                                       rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


class TestFlashAttention:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_gqa_and_causal(self, hq, hkv, causal):
        q = rand(KEY, (2, hq, 128, 32))
        k = rand(jax.random.fold_in(KEY, 1), (2, hkv, 128, 32))
        v = rand(jax.random.fold_in(KEY, 2), (2, hkv, 128, 32))
        out = ops.flash_attention(q, k, v, causal=causal, bq=64, bk=64)
        expect = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("sq", [64, 100, 192])
    def test_padded_lengths(self, sq):
        q = rand(KEY, (1, 2, sq, 32))
        k = rand(jax.random.fold_in(KEY, 1), (1, 2, sq, 32))
        v = rand(jax.random.fold_in(KEY, 2), (1, 2, sq, 32))
        out = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
        expect = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-4)

    def test_bf16(self):
        q = rand(KEY, (1, 4, 128, 64), jnp.bfloat16)
        k = rand(jax.random.fold_in(KEY, 1), (1, 2, 128, 64), jnp.bfloat16)
        v = rand(jax.random.fold_in(KEY, 2), (1, 2, 128, 64), jnp.bfloat16)
        out = ops.flash_attention(q, k, v)
        expect = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32),
            **tol(jnp.bfloat16),
        )


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------


def make_vm(page_size=8, num_pages=64, max_pages=8, max_seqs=4):
    return VirtualMemory(VMemConfig(
        page_size=page_size, num_pages=num_pages,
        max_pages_per_seq=max_pages, max_seqs=max_seqs,
    ))


class TestPagedDecodeAttention:
    @pytest.mark.parametrize("lens", [[13, 40, 1], [8, 8, 8], [64, 3, 17]])
    @pytest.mark.parametrize("g", [1, 4])
    def test_vs_ref(self, lens, g):
        vm = make_vm()
        for i, L in enumerate(lens):
            vm.map_seq(i, L)
        b, hkv, d = len(lens), 2, 32
        k_pool = rand(KEY, (64, 8, hkv, d))
        v_pool = rand(jax.random.fold_in(KEY, 1), (64, 8, hkv, d))
        q = rand(jax.random.fold_in(KEY, 2), (b, hkv, g, d))
        pt, sl = vm.device_page_table(), vm.device_seq_lens()
        out = ops.paged_decode_attention(q, k_pool, v_pool, pt, sl, page_size=8)
        expect = ref.paged_decode_attention_ref(
            q, k_pool, v_pool, pt, sl, page_size=8
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-4)

    def test_paged_equals_contiguous(self):
        """Attention through scattered physical pages == contiguous KV."""
        vm = make_vm()
        # map/unmap to scramble physical frame order
        vm.map_seq(9, 40)
        vm.unmap_seq(9)
        vm.map_seq(0, 30)
        b, hkv, g, d = 1, 2, 2, 32
        L = 30
        k_lin = rand(KEY, (1, hkv, L, d))
        v_lin = rand(jax.random.fold_in(KEY, 1), (1, hkv, L, d))
        q = rand(jax.random.fold_in(KEY, 2), (b, hkv, g, d))
        # place linear KV into the pool through the page table
        k_pool = np.zeros((64, 8, hkv, d), np.float32)
        v_pool = np.zeros((64, 8, hkv, d), np.float32)
        phys = vm.translate(0, np.arange(L))
        k_pool.reshape(-1, hkv, d)[phys] = np.asarray(k_lin[0].swapaxes(0, 1))
        v_pool.reshape(-1, hkv, d)[phys] = np.asarray(v_lin[0].swapaxes(0, 1))
        out = ops.paged_decode_attention(
            q, jnp.asarray(k_pool), jnp.asarray(v_pool),
            vm.device_page_table(), vm.device_seq_lens(), page_size=8,
        )
        # contiguous oracle: dense attention of q over k_lin
        qf = q.reshape(1, hkv * g, 1, d)
        expect = ref.flash_attention_ref(
            qf, k_lin, v_lin, causal=False
        ).reshape(b, hkv, g, d)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-4)

    def test_empty_sequence_outputs_zero(self):
        vm = make_vm()
        vm.map_seq(0, 16)
        pt = vm.device_page_table()
        sl = jnp.array([16, 0, 0, 0], jnp.int32)  # slots 1..3 empty
        k_pool = rand(KEY, (64, 8, 2, 32))
        v_pool = rand(jax.random.fold_in(KEY, 1), (64, 8, 2, 32))
        q = rand(jax.random.fold_in(KEY, 2), (4, 2, 2, 32))
        out = ops.paged_decode_attention(q, k_pool, v_pool, pt, sl, page_size=8)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_array_equal(np.asarray(out[1:]), 0.0)


def make_stacked_case(kv, hkv, *, layers=3, page=4, g=2, d=16, seed=0):
    """Stacked ``[L, P, page, Hkv, D]`` pools and a table whose rows hold
    unmapped (-1) tails, an empty sequence and pages past ``seq_len``."""
    lens = np.array([9, 0, 21, 3], np.int32)
    max_pages = 7
    n_frames = 24
    rng = np.random.default_rng(seed)
    frames = rng.permutation(n_frames)
    table = np.full((len(lens), max_pages), -1, np.int32)
    fi = 0
    for row, n in enumerate(lens):
        # one mapped page past seq_len on every row
        need = -(-int(n) // page) + 1
        table[row, :need] = frames[fi: fi + need]
        fi += need
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 3)
    shape = (layers, n_frames, page, hkv, d)
    if kv == "int8":
        k = jax.random.randint(ks[0], shape, -127, 128).astype(jnp.int8)
        v = jax.random.randint(ks[1], shape, -127, 128).astype(jnp.int8)
    else:
        k, v = rand(ks[0], shape, jnp.bfloat16), rand(ks[1], shape, jnp.bfloat16)
    q = rand(ks[2], (len(lens), hkv, g, d), jnp.bfloat16)
    return q, k, v, jnp.asarray(table), jnp.asarray(lens)


class TestPagedDecodeAttentionStacked:
    """The kernel reads layer ``layer`` of a stacked pool in place: equal
    to the 4-D call on that layer's pool, and to the oracle."""

    @pytest.mark.parametrize("window", [None, 6])
    @pytest.mark.parametrize("hkv", [1, 4])
    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    def test_stack_equals_layer_slice(self, kv, hkv, window):
        q, k, v, pt, sl = make_stacked_case(kv, hkv, seed=hkv)
        kw = dict(page_size=4, window=window,
                  kv_scale=1 / 24 if kv == "int8" else None)
        at = jax.jit(lambda li: ops.paged_decode_attention(
            q, k, v, pt, sl, li, **kw))
        for li in range(k.shape[0]):
            out = at(jnp.int32(li))
            one = ops.paged_decode_attention(q, k[li], v[li], pt, sl, **kw)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(one))
            expect = ops.paged_decode_attention(
                q, k, v, pt, sl, li, use_kernel=False, **kw)
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(expect, np.float32),
                rtol=2e-2, atol=2e-2)
            # the empty sequence attends nothing
            np.testing.assert_array_equal(np.asarray(out[1], np.float32), 0.0)

    def test_layers_differ(self):
        """The index map follows ``layer``: each layer's pool gives its
        own output."""
        q, k, v, pt, sl = make_stacked_case("bf16", 4, seed=5)
        outs = [np.asarray(ops.paged_decode_attention(
            q, k, v, pt, sl, li, page_size=4), np.float32)
            for li in range(k.shape[0])]
        assert not np.allclose(outs[0], outs[1])
        assert not np.allclose(outs[1], outs[2])


# ---------------------------------------------------------------------------
# paged copy / gather
# ---------------------------------------------------------------------------


class TestPagedCopyGather:
    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.integers(1, 60), min_size=1, max_size=3))
    def test_copy_roundtrip_gather(self, lens):
        """write-through-translation then read-through-translation == id."""
        vm = make_vm(max_seqs=len(lens))
        for i, L in enumerate(lens):
            vm.map_seq(i, L)
        w = 4
        smax = max(lens)
        src = jnp.asarray(
            np.random.default_rng(0).normal(size=(len(lens), smax, w))
        ).astype(jnp.float32)
        pool = jnp.zeros((64, 8, w))
        pool = ops.paged_copy(
            src, pool, vm.device_page_table(), jnp.asarray(lens),
            page_size=8,
        )
        for i, L in enumerate(lens):
            row = vm.device_page_table()[i]
            got = ops.paged_gather(
                pool, row, jnp.arange(L), page_size=8
            )
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(src[i, :L]), rtol=0, atol=0
            )

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.integers(0, 49), min_size=1, max_size=32))
    def test_gather_arbitrary_order(self, positions):
        vm = make_vm()
        vm.map_seq(0, 50)
        pool = rand(KEY, (64, 8, 4))
        row = vm.device_page_table()[0]
        pos = jnp.asarray(positions, jnp.int32)
        out_k = ops.paged_gather(pool, row, pos, page_size=8)
        out_r = ref.paged_gather_ref(pool, row, pos, page_size=8)
        out_c = ops.paged_gather_coalesced(pool, row, pos, page_size=8)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
        np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_r))

    def test_copy_preserves_unrelated_frames(self):
        vm = make_vm()
        vm.map_seq(0, 20)
        pool = jnp.full((64, 8, 2), 3.0)
        src = jnp.ones((1, 20, 2))
        out = ops.paged_copy(
            src, pool, vm.device_page_table()[:1], jnp.array([20]),
            page_size=8,
        )
        mapped = set(vm.seq(0).pages)
        for f in range(64):
            if f not in mapped:
                assert (np.asarray(out[f]) == 3.0).all()

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 37), st.integers(0, 18)),
                    min_size=1, max_size=3))
    def test_copy_at_offset_kernel_vs_ref(self, windows):
        """Continuation copy at arbitrary (unaligned) starts: the Pallas
        kernel must match the jnp oracle and the oracle must equal a
        hand-placed write; untouched frames keep their bytes."""
        page, w = 8, 4
        vm = make_vm(max_seqs=len(windows))
        starts = [s for s, _ in windows]
        lens = [n for _, n in windows]
        for i, (s, n) in enumerate(windows):
            vm.map_seq(i, max(s + n, 1))
        rng = np.random.default_rng(7)
        smax = max(max(lens), 1)
        src = jnp.asarray(rng.normal(size=(len(windows), smax, w))
                          ).astype(jnp.float32)
        pool0 = jnp.asarray(rng.normal(size=(64, page, w))
                            ).astype(jnp.float32)
        pt = vm.device_page_table()
        out_k = ops.paged_copy_at(
            src, pool0, pt, jnp.asarray(starts, jnp.int32),
            jnp.asarray(lens, jnp.int32), page_size=page, use_kernel=True,
        )
        out_r = ops.paged_copy_at(
            src, pool0, pt, jnp.asarray(starts, jnp.int32),
            jnp.asarray(lens, jnp.int32), page_size=page, use_kernel=False,
        )
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
        expect = np.asarray(pool0).copy()
        table = np.asarray(pt)
        for i, (s, n) in enumerate(windows):
            for t in range(n):
                pos = s + t
                expect[table[i, pos // page], pos % page] = \
                    np.asarray(src[i, t])
        np.testing.assert_array_equal(np.asarray(out_k), expect)


# ---------------------------------------------------------------------------
# wkv6
# ---------------------------------------------------------------------------


class TestWKV6:
    @pytest.mark.parametrize("bh,t,n", [(2, 32, 16), (4, 48, 16), (1, 128, 64)])
    def test_vs_ref(self, bh, t, n):
        ks = jax.random.split(jax.random.fold_in(KEY, t * n), 5)
        r = rand(ks[0], (bh, t, n), scale=0.5)
        k = rand(ks[1], (bh, t, n), scale=0.5)
        v = rand(ks[2], (bh, t, n), scale=0.5)
        w = jax.nn.sigmoid(rand(ks[3], (bh, t, n)))
        u = rand(ks[4], (bh, n), scale=0.5)
        o_k, s_k = ops.wkv6(r, k, v, w, u, bt=16)
        o_r, s_r = ref.wkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                                   rtol=1e-4, atol=1e-4)

    def test_unaligned_t_padding(self):
        ks = jax.random.split(KEY, 5)
        bh, t, n = 2, 27, 8
        r = rand(ks[0], (bh, t, n), scale=0.5)
        k = rand(ks[1], (bh, t, n), scale=0.5)
        v = rand(ks[2], (bh, t, n), scale=0.5)
        w = jax.nn.sigmoid(rand(ks[3], (bh, t, n)))
        u = rand(ks[4], (bh, n), scale=0.5)
        o_k, s_k = ops.wkv6(r, k, v, w, u, bt=8)
        o_r, s_r = ref.wkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   rtol=1e-4, atol=1e-4)
        # padded identity steps must not corrupt the carried state
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                                   rtol=1e-4, atol=1e-4)

    def test_chunked_equals_monolithic(self):
        """State handoff across chunks (the serving decode contract)."""
        ks = jax.random.split(KEY, 5)
        bh, t, n = 2, 64, 16
        r = rand(ks[0], (bh, t, n), scale=0.5)
        k = rand(ks[1], (bh, t, n), scale=0.5)
        v = rand(ks[2], (bh, t, n), scale=0.5)
        w = jax.nn.sigmoid(rand(ks[3], (bh, t, n)))
        u = rand(ks[4], (bh, n), scale=0.5)
        o_full, s_full = ops.wkv6(r, k, v, w, u, bt=16)
        o1, s1 = ops.wkv6(r[:, :40], k[:, :40], v[:, :40], w[:, :40], u, bt=8)
        o2, s2 = ops.wkv6(r[:, 40:], k[:, 40:], v[:, 40:], w[:, 40:], u, s1, bt=8)
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate([o1, o2], axis=1)),
            np.asarray(o_full), rtol=1e-4, atol=1e-4,
        )
        np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                                   rtol=1e-4, atol=1e-4)


class TestWKV6ChunkedKernel:
    """Chunk-parallel WKV Pallas kernel (flash-linear-attention form)."""

    @pytest.mark.parametrize("bh,t,n,chunk", [
        (2, 64, 16, 16), (4, 128, 16, 32), (1, 96, 32, 32),
    ])
    def test_vs_sequential_ref(self, bh, t, n, chunk):
        from repro.kernels.wkv6_chunked import wkv6_chunked

        ks = jax.random.split(jax.random.fold_in(KEY, t * n), 6)
        r = rand(ks[0], (bh, t, n), scale=0.5)
        k = rand(ks[1], (bh, t, n), scale=0.5)
        v = rand(ks[2], (bh, t, n), scale=0.5)
        w = jax.nn.sigmoid(rand(ks[3], (bh, t, n)) - 1.0)
        u = rand(ks[4], (bh, n), scale=0.5)
        s0 = rand(ks[5], (bh, n, n), scale=0.1).astype(jnp.float32)
        o_k, s_k = wkv6_chunked(r, k, v, w, u, s0, chunk=chunk)
        o_r, s_r = ref.wkv6_ref(r, k, v, w, u, s0)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                                   rtol=3e-4, atol=3e-4)

    def test_extreme_decay_is_stable(self):
        """Near-zero decay underflows gracefully (exponents <= 0)."""
        from repro.kernels.wkv6_chunked import wkv6_chunked

        ks = jax.random.split(KEY, 5)
        bh, t, n = 2, 64, 16
        r = rand(ks[0], (bh, t, n), scale=0.5)
        k = rand(ks[1], (bh, t, n), scale=0.5)
        v = rand(ks[2], (bh, t, n), scale=0.5)
        w = jnp.full((bh, t, n), 1e-6)  # catastrophic decay
        u = rand(ks[4], (bh, n), scale=0.5)
        o_k, s_k = wkv6_chunked(r, k, v, w, u, chunk=16)
        assert np.isfinite(np.asarray(o_k)).all()
        assert np.isfinite(np.asarray(s_k)).all()
        o_r, s_r = ref.wkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   rtol=1e-3, atol=1e-3)
