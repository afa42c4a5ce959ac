"""AOT-bucketed prefill (``ServeConfig.aot_buckets``): bucket and row
ladder selection boundaries, exact pad accounting, token identity vs the
unbucketed engine, and module-cache keying across model twins.

The contract (see ``repro/serve/executor.py``): every prefill /
continuation dispatch whose burst-aligned width fits a configured bucket
runs through an executable compiled AT ENGINE BUILD (``aot_hits``); a
plain prefill of n admitted rows runs the smallest rung of the row ladder
(``row_buckets``) that holds n, a continuation ``max_batch`` rows; pads
are numerically inert (greedy streams bit-identical to the plain
shape-keyed jit path), wider batches fall back loudly (``aot_misses``),
and executables are shared module-wide per (model twin, mesh, kind,
rows, bucket, geometry) — never re-lowered per engine.
"""

import glob
import types

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import Engine, ServeConfig, ServeRequest
from repro.serve.executor import _AOT_CACHE, row_buckets, select_bucket

pytestmark = pytest.mark.slo

KEY = jax.random.PRNGKey(0)

GEOM = dict(page_size=4, num_pages=64, max_pages_per_seq=16, max_batch=3)
#: 16 lanes: the row ladder is (1, 4, 16), as in the chat cell
WIDE = dict(GEOM, num_pages=160, max_batch=16)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("qwen2-7b", reduced=True)
    model = build_model(cfg, remat=False)
    return cfg, model, model.init(KEY)


def _reqs(cfg, lens, max_new=6):
    rng = np.random.default_rng(11)
    return [
        ServeRequest(
            prompt=rng.integers(0, cfg.vocab_size, size=n).astype(np.int32),
            max_new_tokens=max_new, req_id=i,
        )
        for i, n in enumerate(lens)
    ]


def _tokens(results):
    return {rid: [int(np.asarray(t)) for t in r.tokens]
            for rid, r in results.items()}


class TestSelectBucket:
    def test_boundaries(self):
        assert select_bucket(8, (8, 16)) == 8      # exact fit
        assert select_bucket(9, (8, 16)) == 16     # next bucket up
        assert select_bucket(16, (8, 16)) == 16
        assert select_bucket(17, (8, 16)) is None  # beyond every bucket
        assert select_bucket(1, (8, 16)) == 8

    def test_no_buckets(self):
        assert select_bucket(4, None) is None
        assert select_bucket(4, ()) is None


class TestRowBuckets:
    @pytest.mark.parametrize("max_batch, ladder", [
        (1, (1,)),
        (3, (1, 3)),
        (16, (1, 4, 16)),
        (17, (1, 4, 16, 17)),
        (64, (1, 4, 16, 64)),
    ])
    def test_ladder(self, max_batch, ladder):
        """Powers of 4 below max_batch, then max_batch itself."""
        assert row_buckets(max_batch) == ladder

    @pytest.mark.parametrize("nrows, rows, length, bucket", [
        (1, 1, 5, 8),
        (2, 4, 8, 8),
        (4, 4, 9, 16),       # aligned width 12 -> bucket 16
        (5, 16, 3, 8),
        (16, 16, 16, 16),
    ])
    def test_selection(self, model_and_params, nrows, rows, length, bucket):
        """An admission of ``nrows`` prompts of ``length`` tokens picks
        the smallest compiled rung and bucket that hold it; a continuation
        of the same rows stays at max_batch rows."""
        _, model, params = model_and_params
        ex = Engine(model, params,
                    ServeConfig(aot_buckets=(8, 16), **WIDE)).executor
        reqs = [types.SimpleNamespace(prompt=np.zeros(length, np.int32))
                for _ in range(nrows)]
        exe, got_rows, got_bucket = ex._select_aot("prefill", reqs)
        assert (got_rows, got_bucket) == (rows, bucket)
        assert exe is ex._aot[("prefill", rows, bucket)]
        exe, got_rows, got_bucket = ex._select_aot("continue", reqs)
        assert (got_rows, got_bucket) == (16, bucket)
        assert exe is ex._aot[("continue", 16, bucket)]
        assert ex.counters.get("aot_hits") == 2
        assert ex.counters.get("aot_misses") == 0


class TestAotEngine:
    def test_token_identity_and_no_misses(self, model_and_params):
        """Bucket padding must be invisible in the greedy streams, and
        every dispatch must hit a build-time executable."""
        cfg, model, params = model_and_params
        lens = (5, 7, 4, 11, 8)                  # spans both buckets
        plain = Engine(model, params, ServeConfig(**GEOM))
        for r in _reqs(cfg, lens):
            plain.submit(r)
        want = _tokens(plain.drain())
        assert plain.counters.get("aot_hits") == 0    # unbucketed: no counting
        assert plain.counters.get("aot_misses") == 0

        aot = Engine(model, params,
                     ServeConfig(aot_buckets=(8, 16), **GEOM))
        for r in _reqs(cfg, lens):
            aot.submit(r)
        got = _tokens(aot.drain())
        assert got == want
        assert aot.counters.get("aot_hits") > 0
        assert aot.counters.get("aot_misses") == 0
        assert aot.counters.get("bucket_pad_tokens") > 0

    def test_exact_pad_accounting_single_request(self, model_and_params):
        """One 5-token prompt under bucket 8, max_batch 3 (row ladder
        (1, 3)): the dispatch runs the one-row rung, 1 row of
        burst-aligned width 8 in 1 row x 8 columns — exactly
        rows*bucket - nrows*aligned == 0 pad tokens and no pad row."""
        cfg, model, params = model_and_params
        eng = Engine(model, params, ServeConfig(aot_buckets=(8,), **GEOM))
        eng.submit(_reqs(cfg, (5,))[0])
        eng.drain()
        assert eng.counters.get("aot_hits") == 1
        assert eng.counters.get("bucket_pad_tokens") == 1 * 8 - 1 * 8
        assert eng.counters.get("prefill_pad_rows") == 0

    def test_exact_pad_accounting_two_rows_of_sixteen(self,
                                                      model_and_params):
        """Two prompts (5 and 7 tokens) admitted together under bucket 8,
        max_batch 16: the dispatch runs the 4-row rung, not 16 rows —
        4*8 - 2*8 == 16 pad tokens, 2 pad rows."""
        cfg, model, params = model_and_params
        eng = Engine(model, params, ServeConfig(aot_buckets=(8,), **WIDE))
        for r in _reqs(cfg, (5, 7)):
            eng.submit(r)
        eng.drain()
        assert eng.counters.get("aot_hits") == 1
        assert eng.counters.get("bucket_pad_tokens") == 4 * 8 - 2 * 8
        assert eng.counters.get("prefill_pad_rows") == 4 - 2

    @pytest.mark.parametrize("kv_dtype", ["native", "int8"])
    @pytest.mark.parametrize("nrows, rung", [(1, 1), (2, 4), (3, 4),
                                             (5, 16)])
    def test_row_ladder_token_identity(self, model_and_params, nrows, rung,
                                       kv_dtype):
        """An admission of ``nrows`` rows, run as the smallest rung of the
        (1, 4, 16) ladder, streams the same greedy tokens as the
        unbucketed engine, over native-dtype and int8 pools."""
        cfg, model, params = model_and_params
        lens = (5, 7, 4, 11, 8)[:nrows]
        plain = Engine(model, params,
                       ServeConfig(kv_dtype=kv_dtype, **WIDE))
        for r in _reqs(cfg, lens):
            plain.submit(r)
        want = _tokens(plain.drain())

        aot = Engine(model, params, ServeConfig(
            aot_buckets=(8, 16), kv_dtype=kv_dtype, **WIDE))
        for r in _reqs(cfg, lens):
            aot.submit(r)
        assert _tokens(aot.drain()) == want
        assert aot.counters.get("aot_hits") == 1      # one admission
        assert aot.counters.get("aot_misses") == 0
        assert aot.counters.get("prefill_pad_rows") == rung - nrows

    def test_pack_span_carries_the_rung(self, model_and_params, tmp_path):
        """The prefill's ``serve.exec.pack`` span names the dispatched
        rung and bucket in its metadata: a 2-row admission under 16 lanes
        packs 4 rows of bucket 8."""
        cfg, model, params = model_and_params
        eng = Engine(model, params, ServeConfig(aot_buckets=(8,), **WIDE))

        def admit_two():
            for r in _reqs(cfg, (5, 7), max_new=1):
                eng.submit(r)
            eng.drain()

        admit_two()                                # compile outside
        with jax.profiler.trace(str(tmp_path)):
            admit_two()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        packs = [dict(e.stats)
                 for plane in jax.profiler.ProfileData.from_file(path).planes
                 for line in plane.lines for e in line.events
                 if e.name == "serve.exec.pack"]
        assert {"rows": 4, "bucket": 8} in packs

    def test_no_misses_over_every_row_count(self, model_and_params):
        """Admissions of every n in 1..max_batch each hit a build-time
        executable: the ladder covers every admitted row count."""
        cfg, model, params = model_and_params
        eng = Engine(model, params, ServeConfig(aot_buckets=(8,), **WIDE))
        for n in range(1, WIDE["max_batch"] + 1):
            for r in _reqs(cfg, (4,) * n, max_new=1):
                eng.submit(r)
            eng.drain()
        assert eng.counters.get("aot_misses") == 0
        assert eng.counters.get("aot_hits") == WIDE["max_batch"]
        # n = 2..4 pad to 4 rows (3 pad rows), n = 5..16 to 16 (66)
        assert eng.counters.get("prefill_pad_rows") == 3 + 66

    def test_overlong_prompt_is_a_counted_miss(self, model_and_params):
        """A prompt whose aligned width exceeds every bucket falls back
        to the shape-keyed jit — counted, completed, token-identical."""
        cfg, model, params = model_and_params
        plain = Engine(model, params, ServeConfig(**GEOM))
        for r in _reqs(cfg, (9,)):
            plain.submit(r)
        want = _tokens(plain.drain())

        eng = Engine(model, params, ServeConfig(aot_buckets=(8,), **GEOM))
        for r in _reqs(cfg, (9,)):                # aligned width 12 > 8
            eng.submit(r)
        got = _tokens(eng.drain())
        assert got == want
        assert eng.counters.get("aot_misses") == 1
        assert eng.counters.get("aot_hits") == 0
        assert eng.counters.get("bucket_pad_tokens") == 0

    def test_continuation_prefill_rides_the_buckets(self, model_and_params):
        """share_prefix forks prefill only the divergent chunk through
        ``admit_forked_batch`` — that continuation dispatch must hit the
        'continue' executable, and streams must match the unbucketed
        forked engine."""
        cfg, model, params = model_and_params
        rng = np.random.default_rng(5)
        prefix = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)

        def forked(serve_cfg):
            eng = Engine(model, params, serve_cfg)
            eng.preload_prefix(prefix)
            for r in _reqs(cfg, (5, 6)):
                r.share_prefix = True
                eng.submit(r)
            return eng

        plain = forked(ServeConfig(**GEOM))
        want = _tokens(plain.drain())
        aot = forked(ServeConfig(aot_buckets=(8,), **GEOM))
        got = _tokens(aot.drain())
        assert got == want
        assert aot.counters.get("aot_misses") == 0
        assert aot.counters.get("aot_hits") > 0
        assert aot.counters.get("continuation_prefill_tokens") == \
            plain.counters.get("continuation_prefill_tokens")


class TestModuleCacheKeying:
    def test_same_twin_shares_new_twin_recompiles(self, model_and_params):
        """The module cache keys on (step-model twin, mesh, kind, bucket,
        geometry): a second identical engine adds NOTHING and binds the
        same executables; an int8-KV engine (a different model twin with
        different pool dtypes) adds exactly its own entries; a new bucket
        size adds exactly one entry per (kind, rows) rung.  A geometry no other test
        uses (max_batch=2), so the entry-count deltas are exact
        regardless of what ran before in this process."""
        cfg, model, params = model_and_params
        geom = dict(GEOM, max_batch=2)
        a = Engine(model, params, ServeConfig(aot_buckets=(8,), **geom))
        n0 = len(_AOT_CACHE)

        b = Engine(model, params, ServeConfig(aot_buckets=(8,), **geom))
        assert len(_AOT_CACHE) == n0              # full reuse
        assert all(b.executor._aot[k] is a.executor._aot[k]
                   for k in a.executor._aot)

        # max_batch 2: prefill rows (1, 2), continuation rows (2,)
        wider = Engine(model, params,
                       ServeConfig(aot_buckets=(8, 16), **geom))
        assert len(_AOT_CACHE) == n0 + 3          # (prefill,1|2,16),
        #                                           (continue,2,16)
        assert wider.executor._aot[("prefill", 1, 8)] is \
            a.executor._aot[("prefill", 1, 8)]

        n1 = len(_AOT_CACHE)
        q = Engine(model, params,
                   ServeConfig(aot_buckets=(8,), kv_dtype="int8", **geom))
        assert len(_AOT_CACHE) == n1 + 3          # int8 twin: own executables
        assert q.executor._aot[("prefill", 1, 8)] is not \
            a.executor._aot[("prefill", 1, 8)]
