"""The readers of the program's host spans and admission counters, on a
small synthetic trace with hand-computed values."""

import types

import pytest

import run
import trace_reduce as tr

S = tr.Span
EXEC = ("exec.host_idle_share.online", "exec.host_idle_share.offline")
SCHED = ("sched.host_idle_share.online", "sched.host_idle_share.offline")


def _trace():
    # window [0, 10); the chip runs [1, 3), [5, 6) and [7.5, 8), so it
    # idles [0, 1), [3, 5), [6, 7.5) and [8, 10): 6.5 s
    ops = [S("fusion.1", 1.0, 3.0), S("paged_decode_attention.2", 5.0, 6.0),
           S("flash_attention.3", 7.5, 8.0)]
    host = [S(tr.WINDOW, 0.0, 10.0), S("chipbench.step", 0.0, 7.4),
            # the harness's own wrapper around a data-plane call, and the
            # program's spans inside it
            S("exec.decode", 0.1, 4.9),
            S("serve.sched.step", 0.2, 7.3),
            S("serve.exec.decode", 0.3, 4.8),
            # [0, 1): midpoint 0.5 under a JAX runtime event
            S("PjitFunction(_decode_multi_impl)", 0.4, 0.6),
            # [3, 5): midpoint 4 under the executor's read back
            S("serve.exec.readback", 3.5, 4.5),
            # [6, 7.5): midpoint 6.75 under the scheduler's commit
            S("serve.sched.commit", 6.5, 7.0),
            # [8, 10): midpoint 9 in the harness, which no program
            # metric counts
            S("chipbench.observe", 8.5, 9.5)]
    return tr.Trace(ops, [], host, chips=1)


def _rec(trace=None, counters=None):
    return types.SimpleNamespace(trace=trace, counters=counters or {})


def _read(name, rec):
    return run.reader(name).read(rec)


@pytest.mark.parametrize("name", EXEC)
def test_executor_idle_share(name):
    red = tr.reduce(_trace())
    assert 100 * (1 - red.busy_s / red.window_s) == pytest.approx(65.0)
    # 1 s under PjitFunction(...) and 2 s under serve.exec.readback
    assert _read(name, _rec(red)) == pytest.approx(30.0)


@pytest.mark.parametrize("name", SCHED)
def test_scheduler_idle_share(name):
    # 1.5 s under serve.sched.commit
    assert _read(name, _rec(tr.reduce(_trace()))) == pytest.approx(15.0)


def _reduction(idle_by_host):
    return tr.Reduction((0.0, 10.0), 10.0 - sum(idle_by_host.values()),
                        {}, {}, [], idle_by_host)


@pytest.mark.parametrize("name", EXEC)
def test_executor_counts_the_jax_runtime_events(name):
    red = _reduction({
        "serve.exec.wait": 0.5, "serve.exec.ptab_sync": 0.25,
        "np.asarray(jax.Array)": 0.125, "DevicePutWithSharding": 0.0625,
        "DevicePut": 0.0625, "shard_args": 0.25, "ParseArguments": 0.25,
        "PjitFunction(_take)": 0.5,
        # not the executor's
        "serve.sched.plan": 1.0, "exec.decode": 2.0, "chipbench.step": 1.0,
        "(no host event)": 0.5})
    assert _read(name, _rec(red)) == pytest.approx(20.0)


@pytest.mark.parametrize("name", EXEC + SCHED)
def test_zero_without_program_spans_and_none_without_a_trace(name):
    # a program without the spans (the harness's wrappers only) reads 0
    red = _reduction({"exec.decode": 1.8, "chipbench.observe": 0.2})
    assert _read(name, _rec(red)) == 0.0
    assert _read(name, _rec(None)) is None


def test_queue_wait_per_admission():
    rec = _rec(counters={"admitted": 4, "queue_wait_us": 10_000})
    assert _read("sched.queue_wait_ms", rec) == pytest.approx(2.5)
    # no admission in the window, or a program without the counters
    assert _read("sched.queue_wait_ms",
                 _rec(counters={"admitted": 0, "queue_wait_us": 0})) is None
    assert _read("sched.queue_wait_ms", _rec()) is None
