"""Load generators and the latency arithmetic."""

import numpy as np
import pytest

import latency
import traffic

MIX = {"arrivals": "poisson", "rate_per_s": 2.0, "lead_in_s": 3,
       "prompt": {"median": 100, "sigma": 0.7, "min": 20, "max": 300},
       "output": {"median": 10, "sigma": 0.9, "min": 2, "max": 64},
       "queue_depth": 4, "stream_len": 50}


def test_open_loop_is_deterministic_per_seed():
    a = traffic.open_loop(MIX, 1000, 20.0, seed=2**31 + 12345)
    b = traffic.open_loop(MIX, 1000, 20.0, seed=2**31 + 12345)
    assert [x.due for x in a] == [x.due for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [x.max_new for x in a] == [x.max_new for x in b]
    c = traffic.open_loop(MIX, 1000, 20.0, seed=7)
    assert [x.due for x in a] != [x.due for x in c]


def test_open_loop_schedule_shape():
    items = traffic.open_loop(MIX, 1000, 20.0, seed=3)
    # rate x (lead-in + window) requests, the first due at -lead_in
    assert len(items) == 46
    due = [x.due for x in items]
    assert due[0] == -3.0
    assert all(b >= a for a, b in zip(due, due[1:]))
    # the gaps are stratified quantiles of an exponential of mean 1/rate
    gaps = np.diff(due)
    full = traffic.quantiles_exponential(46, 2.0)
    assert np.mean(full) == pytest.approx(0.5, rel=0.05)
    assert set(np.round(gaps, 9)) <= set(np.round(full, 9))


def test_every_seed_gets_the_same_lengths():
    a = traffic.open_loop(MIX, 1000, 20.0, seed=1)
    b = traffic.open_loop(MIX, 1000, 20.0, seed=2)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)


@pytest.mark.parametrize("seed", (1, 2**31 + 7))
def test_balanced_order_deals_one_value_per_stratum(seed):
    rng = np.random.default_rng(seed)
    values = np.arange(40.0)
    out = traffic.shuffle(values, 4, rng)
    assert sorted(out) == list(values)
    # 10 groups of 4: each holds one value from each quarter of the range
    for g in range(10):
        assert sorted(v // 10 for v in out[4 * g:4 * g + 4]) == [0, 1, 2, 3]
    mix = {**MIX, "stratify_block": 4}
    a = traffic.open_loop(mix, 1000, 20.0, seed)
    b = traffic.open_loop(MIX, 1000, 20.0, seed)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)


def test_lognormal_clip_holds():
    q = traffic.quantiles_lognormal(1000, 100, 2.0, 20, 300)
    assert q.min() == 20 and q.max() == 300
    assert np.median(q) == pytest.approx(100, abs=1)
    items = traffic.open_loop(MIX, 1000, 50.0, seed=4)
    assert all(20 <= len(x.prompt) <= 300 for x in items)
    assert all(2 <= x.max_new <= 64 for x in items)
    assert all(0 <= x.prompt.min() and x.prompt.max() < 1000 for x in items)


def test_closed_loop_keeps_the_queue_deep():
    loop = traffic.ClosedLoop(MIX, 1000, seed=5)
    queued = 0
    for admitted in (4, 0, 1, 3, 2, 4):
        queued += len(loop.top_up(queued))
        assert queued >= loop.depth
        queued -= admitted
    # distinct requests until the stream runs out, then a loud error
    with pytest.raises(RuntimeError):
        for _ in range(50):
            loop.top_up(0)


def _log(due, commits, max_new=8):
    return latency.RequestLog(prompt_len=5, max_new=max_new, due=due,
                              submitted=due, commits=commits)


def test_ttft_tpot_and_rate_on_hand_made_stamps():
    logs = [
        _log(1.0, [(1.5, 1), (2.5, 4), (3.0, 4)]),    # due in window
        _log(2.0, [(4.0, 1), (12.0, 2)]),             # second commit after
        _log(8.0, []),                                # never got a token
        _log(-1.0, [(0.5, 1), (1.0, 8)]),             # begun before window
        _log(-3.0, [(-2.0, 1), (0.2, 8), (0.6, 8), (1.0, 4)]),  # a horizon
        _log(11.0, [(11.5, 1)]),                      # due after the window
    ]
    t0, t1, t_end = 0.0, 10.0, 13.0
    ttft, missing = latency.ttfts(logs, t0, t1, t_end)
    assert sorted(ttft) == pytest.approx([0.5, 2.0, 5.0])
    assert missing == 1
    # tpot: request 0: (3.0 - 1.5) / (9 - 1); request 3: (1.0-0.5)/(9-1);
    # request 5's first commit inside the window holds a whole horizon of
    # 8 tokens made before its stamp: (1.0 - 0.2) / (20 - 8)
    # request 1 has one commit instant inside the window: no sample
    assert sorted(latency.tpots(logs, t0, t1)) == pytest.approx(
        [0.5 / 8, 0.8 / 12, 1.5 / 8])
    assert latency.tokens_in(logs, t0, t1) == 9 + 1 + 9 + 20
    assert latency.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
