"""The comparison that decides ``correct``, driven through a whole run on
the CPU at a tiny size (the look for a chip skipped, Pallas interpreted):
a sound run passes, a served token altered where it is produced fails,
and the fp8 control, put in the program's place, fails."""

import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

import reference
import run

ROOT = pathlib.Path(run.__file__).resolve().parents[1]
#: at this size, over 8 seeds, the program's widest gap reads at most
#: 0.0207 and the fp8 control's at least 0.127 (int8's 0.0188 does not
#: separate here)
TINY_LIMIT = 0.06
SEEDS = (2**31 + 5, 11)


def make_tiny_root(root: pathlib.Path) -> pathlib.Path:
    """A benchmark tree with two tiny qwen2-shaped cells under ``root``:
    ``tiny.tiny`` (the chat mix, open loop) and ``tiny.batch`` (the
    offline mix, closed loop)."""
    (root / "chipbench" / "configs").mkdir(parents=True)
    (root / "chipbench" / "traffic").mkdir(parents=True)
    cfg = json.loads((ROOT / "chipbench/configs/qwen2-7b.json").read_text())
    cfg.update(name="tiny", hidden_size=128, intermediate_size=256,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               num_hidden_layers=2, vocab_size=512,
               correct={"max_logit_gap": TINY_LIMIT})
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "chipbench/traffic/chat.json").read_text())
    mix.update(rate_per_s=3.0, lead_in_s=1,
               prompt={"median": 40, "sigma": 0.5, "min": 8, "max": 64},
               output={"median": 24, "sigma": 0.5, "min": 4, "max": 64},
               max_batch=4, aot_buckets=[32, 64], reach_tokens=128,
               pool_tokens=512, check={"rows": 8, "length": 256,
                                       "min_tokens": 10})
    (root / "chipbench/traffic/tiny.json").write_text(json.dumps(mix))
    batch = json.loads((ROOT / "chipbench/traffic/offline.json").read_text())
    batch.update(queue_depth=4, stream_len=200, warm_steps=2,
                 prompt={"median": 40, "sigma": 0.5, "min": 8, "max": 64},
                 output={"median": 30, "sigma": 0.5, "min": 8, "max": 64},
                 max_batch=4, aot_buckets=[32, 64], reach_tokens=128,
                 pool_tokens=512, check={"rows": 8, "length": 256,
                                         "min_tokens": 10})
    (root / "chipbench/traffic/batch.json").write_text(json.dumps(batch))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "chipbench/configs/tiny.json", "why": "t"}]
    bench["workloads"] = [{"name": f"tiny.{mix}", "config": "tiny",
                           "traffic": mix, "chips": 1, "why": "t"}
                          for mix in ("tiny", "batch")]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench"))


def _run(root, seed, hook=None):
    return run.run("tiny.tiny", seed, 3.0, False, root=root,
                   require_chip=False, hook=hook)


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_is_correct(tiny_root, seed):
    out = _run(tiny_root, seed)
    assert out["correct"], out["checks"]
    assert out["checks"]["compared_tokens"]["value"] >= 10
    assert set(out["metrics"]) == {"ttft_p75_ms", "output_tok_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_sound_closed_loop_run_is_correct(tiny_root):
    out = run.run("tiny.batch", SEEDS[0], 3.0, False, root=tiny_root,
                  require_chip=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 4 and out["failed"] == 0
    assert out["metrics"]["output_tok_s"]["value"] > 0


def _alter_tokens(engine):
    """Fault: every served decode token is replaced where it is produced
    (the device feeds its own token on; only what is served changes)."""
    ex = engine.executor
    vocab = engine.model.cfg.vocab_size
    multi, single = ex.decode_multi, ex.decode

    def decode_multi(plan):
        return (multi(plan) + 1) % vocab

    def decode(tokens, pre_lens, active):
        return (single(tokens, pre_lens, active) + 1) % vocab

    ex.decode_multi, ex.decode = decode_multi, decode


def test_altered_token_is_not_correct(tiny_root):
    out = _run(tiny_root, SEEDS[0], hook=_alter_tokens)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > 10 * TINY_LIMIT


def _keep_kv_state(engine):
    """Fault: every decode step returns the KV pools unchanged (the new
    tokens' keys and values are never written)."""
    ex = engine.executor
    multi, single = ex.decode_multi, ex.decode

    def unchanged(fn):
        def call(*args):
            before = (jnp.copy(ex.kv.k_pools), jnp.copy(ex.kv.v_pools))
            out = fn(*args)
            ex.kv = ex.kv._replace(k_pools=before[0], v_pools=before[1])
            return out
        return call

    ex.decode_multi, ex.decode = unchanged(multi), unchanged(single)


def test_kv_state_left_unchanged_is_not_correct(tiny_root):
    out = _run(tiny_root, SEEDS[0], hook=_keep_kv_state)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_control_is_not_correct(tiny_root, seed):
    """The fp8 reference, put in the program's place, fails the run's own
    comparison (a sound run of the same seed passes it, above)."""
    out = run.run("tiny.tiny", seed, 3.0, False, root=tiny_root,
                  require_chip=False, control="fp8")
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


def test_reference_packs_teacher_forced_rows():
    tokens, targets, valid = reference.pack(
        [(np.array([5, 6, 7]), np.array([8, 9]))], 8)
    assert tokens[0, :5].tolist() == [5, 6, 7, 8, 9]
    assert valid[0].tolist() == [False, False, True, True] + [False] * 4
    assert targets[0, 2:4].tolist() == [8, 9]
    with pytest.raises(ValueError):
        reference.pack([(np.arange(6), np.arange(4))], 8)
