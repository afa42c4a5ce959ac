"""Trace reduction and work counts, on a small synthetic trace and on
hand-computed values."""

import pytest

import trace_reduce as tr
import workcount

S = tr.Span


def _trace():
    # window [0, 10); device ops: [1, 3) and [2, 4) overlap, [6, 7), and
    # [9, 12) runs past the window's close
    ops = [S("fusion.1", 1.0, 3.0), S("paged_decode_attention.2", 2.0, 4.0),
           S("fusion.1", 6.0, 7.0), S("copy.3", 9.0, 12.0)]
    modules = [S("jit__decode_multi_impl", 1.0, 4.0),
               S("jit__prefill_impl", 6.0, 7.0),
               S("jit__decode_impl", 9.0, 12.0)]
    host = [S(tr.WINDOW, 0.0, 10.0), S("chipbench.step", 0.0, 8.0),
            S("exec.decode", 0.5, 4.5), S("chipbench.wait", 8.0, 10.0),
            S("$frame.py:1 f", 4.0, 6.0)]
    return tr.Trace(ops, modules, host, chips=1)


def test_busy_union_and_idle_share():
    red = tr.reduce(_trace())
    # busy [1, 4) + [6, 7) + [9, 10) = 5 s of 10
    assert red.window_s == 10.0
    assert red.busy_s == pytest.approx(5.0)
    assert 1 - red.busy_s / red.window_s == pytest.approx(0.5)


def test_device_time_per_name_and_program():
    red = tr.reduce(_trace())
    assert red.by_op["fusion.1"] == pytest.approx(3.0)
    assert red.by_op["copy.3"] == pytest.approx(1.0)       # clipped
    assert red.op_seconds("paged_decode_attention") == pytest.approx(2.0)
    assert red.module_seconds("_decode_multi_impl",
                              "_decode_impl") == pytest.approx(4.0)
    assert red.module_seconds("_prefill_impl") == pytest.approx(1.0)
    assert tr.top(red.by_op, 1) == [["fusion.1", pytest.approx(3.0)]]


def test_op_names_and_leaf_ops():
    assert tr.op_name("%paged_decode_attention.5 = bf16[16,4,7,128]{3,2,1,0} "
                      "custom-call(s32[16] %get-tuple-element.1037)"
                      ) == "paged_decode_attention.5"
    assert tr.op_name("jit__decode_impl(123)") == "jit__decode_impl(123)"
    # a while op spans its body's ops on the same line: only the body counts
    ops = [S("while.1", 0.0, 5.0), S("fusion.2", 0.0, 2.0),
           S("flash_attention.3", 2.0, 4.5), S("copy.4", 6.0, 7.0)]
    assert [s.name for s in tr.leaves(ops)] == ["fusion.2",
                                                "flash_attention.3", "copy.4"]
    red = tr.reduce(tr.Trace(ops, [], [S(tr.WINDOW, 0.0, 10.0)], chips=1))
    assert "while.1" not in red.by_op
    assert red.busy_s == pytest.approx(6.0)
    assert red.op_seconds("flash_attention") == pytest.approx(2.5)


def test_gaps_attributed_to_innermost_host_event():
    red = tr.reduce(_trace())
    # gap [0, 1): midpoint 0.5 opens exec.decode; [4, 6): chipbench.step
    # ("$" Python frames are skipped); [7, 9): midpoint 8 in chipbench.wait
    assert red.idle_by_host == {
        "exec.decode": pytest.approx(1.0),
        "chipbench.step": pytest.approx(2.0),
        "chipbench.wait": pytest.approx(2.0)}


def test_union_and_gaps_helpers():
    spans = [S("a", 0, 2), S("b", 1, 3), S("c", 5, 6)]
    busy = tr.union(spans, 0.5, 5.5)
    assert busy == [(0.5, 3), (5, 5.5)]
    assert tr.gaps(busy, 0.0, 6.0) == [(0.0, 0.5), (3, 5), (5.5, 6.0)]


def test_window_comes_from_the_host_annotation():
    t = _trace()
    assert tr.window_of(t) == (0.0, 10.0)
    with pytest.raises(ValueError):
        tr.window_of(tr.Trace([], [], [], 1))


CFG = {"num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 8, "hidden_size": 32,
       "intermediate_size": 64, "vocab_size": 100, "torch_dtype": "bfloat16"}


def test_decode_attention_counts_by_hand():
    # two lanes at contexts 10 and 30: per layer 4*Hq*hd*ctx flops
    flops, nbytes = workcount.decode_attention(CFG, [10, 30])
    assert flops == 4 * 4 * 8 * 40 * 2
    # K and V of 40 tokens over 2 KV heads, plus q and o of 2 queries
    assert nbytes == (2 * 2 * 8 * 40 + 2 * 4 * 8 * 2) * 2 * 2


def test_flash_prefill_counts_by_hand():
    # rows of 3 and 5 tokens: 6 + 15 causal pairs
    flops, nbytes = workcount.flash_prefill(CFG, [3, 5])
    assert flops == 4 * 4 * 8 * 21 * 2
    assert nbytes == (2 * 4 + 2 * 2) * 8 * 8 * 2 * 2


def test_model_flops_by_hand():
    per_layer = 2 * 32 * 4 * 8 + 2 * 32 * 2 * 8 + 3 * 32 * 64
    assert workcount.matmul_params(CFG) == 2 * per_layer
    head = 2 * 32 * 100
    body = 2 * 2 * per_layer
    pre = workcount.model_flops(CFG, {"kind": "prefill", "lens": [3, 5]})
    assert pre == body * 8 + head * 2 + workcount.flash_prefill(CFG, [3, 5])[0]
    dec = workcount.model_flops(CFG, {"kind": "decode", "ctxs": [10, 30]})
    assert dec == (body + head) * 2 + workcount.decode_attention(
        CFG, [10, 30])[0]
    # a continuation of 2 tokens after 4 cached: 4*2 + 3 pairs
    cont = workcount.model_flops(
        CFG, {"kind": "continue", "starts": [4], "lens": [2]})
    assert cont == body * 2 + head + 4 * 4 * 8 * 11 * 2


def test_roofline_takes_the_larger_bound():
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert workcount.roofline_seconds(50.0, 1.0, peak) == 0.5
    assert workcount.roofline_seconds(50.0, 20.0, peak) == 2.0
