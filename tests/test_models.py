"""Per-architecture smoke tests (reduced configs) + serving consistency.

Every assigned architecture instantiates its REDUCED config and runs one
forward/train step on CPU asserting output shapes and finiteness (spec
requirement).  The golden consistency tests assert the serving contract:
prefill + paged decode produce exactly the logits of the monolithic forward.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.core import VMemConfig, VirtualMemory
from repro.models import build_model

KEY = jax.random.PRNGKey(0)


def make_batch(cfg, b=2, s=16, key=KEY):
    shape = (b, s + 1, cfg.num_codebooks) if (
        cfg.family == "audio" and cfg.num_codebooks > 1
    ) else (b, s + 1)
    tokens = jax.random.randint(key, shape, 0, cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    if cfg.family == "vlm":
        batch["positions"] = jnp.broadcast_to(jnp.arange(s), (3, b, s))
        batch["vision_embeds"] = (
            jax.random.normal(jax.random.fold_in(key, 1), (b, 4, cfg.d_model))
            * 0.02
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_smoke_train_step(arch):
    """One forward + loss + grad step per assigned architecture."""
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg, remat=False)
    params = model.init(KEY)
    batch = make_batch(cfg)
    loss, metrics = model.loss(params, batch)
    assert np.isfinite(float(loss)), arch
    assert float(loss) > 0
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(leaf, np.float32)).all(), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_smoke_output_shapes(arch):
    cfg = get_config(arch, reduced=True)
    model = build_model(cfg, remat=False)
    params = model.init(KEY)
    batch = make_batch(cfg)
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        h, _ = model.forward(params, batch["tokens"],
                             batch.get("positions"),
                             batch.get("vision_embeds"))
        assert h.shape[:2] == batch["tokens"].shape[:2]
        logits = model.logits_fn(params, h)
    else:
        if cfg.family == "rwkv6":
            h, _ = model.forward(params, batch["tokens"])
        else:
            h = model.forward(params, batch["tokens"])
        logits = h @ params["head"]
    assert logits.shape[-1] == cfg.vocab_size
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def _golden_paged(arch, steps=3, tol=1e-3, num_layers=None, **model_kw):
    """prefill + decode through paged VM == monolithic forward.

    MoE models compare with the drop-free ragged dispatch on both sides
    (the sorted training dispatch drops tokens at capacity by design, so
    it cannot be the serving oracle)."""
    cfg = get_config(arch, reduced=True)
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    kwargs = {"moe_dispatch": "ragged"} if cfg.family == "moe" else {}
    model = build_model(cfg, remat=False, **kwargs, **model_kw)
    params = model.init(KEY)
    B, PROMPT, PAGE = 2, 10, 4
    tok_shape = (B, PROMPT + steps + 1) + (
        (cfg.num_codebooks,) if cfg.family == "audio" and cfg.num_codebooks > 1
        else ()
    )
    tokens = jax.random.randint(jax.random.fold_in(KEY, 7), tok_shape, 0,
                                cfg.vocab_size)
    vm = VirtualMemory(VMemConfig(page_size=PAGE, num_pages=64,
                                  max_pages_per_seq=16, max_seqs=B))
    for i in range(B):
        vm.map_seq(i, PROMPT)
    if cfg.family == "hybrid_rglru":
        state = model.init_state(B, 64, PAGE, 16)
    else:
        state = model.init_kv_state(B, 64, PAGE, 16)
    state = state._replace(page_table=vm.device_page_table())
    plens = jnp.full((B,), PROMPT, jnp.int32)
    logits_p, state = model.prefill(params, tokens[:, :PROMPT], plens, state)

    def fwd_logits(upto):
        if cfg.family in ("dense", "moe", "vlm", "audio"):
            h, _ = model.forward(params, tokens[:, :upto])
            return model.logits_fn(params, h)[:, -1]
        h = model.forward(params, tokens[:, :upto])
        return (h @ params["head"])[:, -1]

    np.testing.assert_allclose(
        np.asarray(logits_p, np.float32),
        np.asarray(fwd_logits(PROMPT), np.float32), rtol=tol, atol=tol,
    )
    for s in range(steps):
        nxt = tokens[:, PROMPT + s]
        for b in range(B):
            vm.append_tokens(b, 1)
        state = state._replace(page_table=vm.device_page_table())
        logits_d, state = model.decode_step(params, nxt, state)
        np.testing.assert_allclose(
            np.asarray(logits_d, np.float32),
            np.asarray(fwd_logits(PROMPT + s + 1), np.float32),
            rtol=tol, atol=tol,
        )


@pytest.mark.parametrize("arch", [
    "qwen2-7b", "granite-moe-1b-a400m", "recurrentgemma-9b", "musicgen-large",
])
def test_golden_paged_serving(arch):
    """Serving through the paged VM is exact vs the monolithic forward."""
    _golden_paged(arch)


@pytest.mark.parametrize("arch", ["qwen2-7b", "llama4-maverick-400b-a17b"])
def test_golden_paged_serving_kernels(arch):
    """The same through the Pallas kernels (interpreted here): decode
    reads each layer's KV out of the stacked pools at that layer's index,
    which interleaved MoE superblocks (llama4, two layers per scan step)
    compute as superblock * 2 + sublayer; four layers make two
    superblocks."""
    _golden_paged(arch, num_layers=4, use_kernels=True)


def test_golden_rwkv_serving():
    """RWKV: recurrent-state serving == monolithic forward."""
    cfg = get_config("rwkv6-7b", reduced=True)
    model = build_model(cfg, remat=False)
    params = model.init(KEY)
    B, PROMPT = 2, 10
    tokens = jax.random.randint(KEY, (B, 16), 0, cfg.vocab_size)
    state = model.init_state(B)
    logits_p, state = model.prefill(
        params, tokens[:, :PROMPT], jnp.full((B,), PROMPT, jnp.int32), state
    )
    h, _ = model.forward(params, tokens[:, :PROMPT])
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray((h @ params["head"])[:, -1]),
        rtol=1e-3, atol=1e-3,
    )
    for s in range(4):
        logits_d, state = model.decode_step(params, tokens[:, PROMPT + s], state)
        h, _ = model.forward(params, tokens[:, :PROMPT + s + 1])
        np.testing.assert_allclose(
            np.asarray(logits_d), np.asarray((h @ params["head"])[:, -1]),
            rtol=2e-3, atol=2e-3,
        )


def test_loss_decreases_dense():
    """A few optimizer steps reduce the loss (end-to-end sanity)."""
    from repro.optim import AdamWConfig, adamw_init, adamw_update

    cfg = get_config("granite-8b", reduced=True)
    model = build_model(cfg, remat=False)
    params = model.init(KEY)
    opt = adamw_init(params)
    opt_cfg = AdamWConfig(base_lr=3e-3, warmup_steps=2, total_steps=30)
    batch = make_batch(cfg, b=4, s=32)
    first = last = None

    @jax.jit
    def step(p, o, b):
        (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(p, b)
        p2, o2, _ = adamw_update(grads, o, p, opt_cfg)
        return p2, o2, loss

    for i in range(15):
        params, opt, loss = step(params, opt, batch)
        if first is None:
            first = float(loss)
        last = float(loss)
    assert last < first * 0.9, (first, last)


def test_mrope_reduces_to_rope_for_text():
    """Text tokens (t==h==w positions) under M-RoPE == standard RoPE."""
    from repro.models.layers import apply_mrope, apply_rope

    x = jax.random.normal(KEY, (2, 8, 4, 16))
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    pos3 = jnp.broadcast_to(pos, (3, 2, 8))
    a = apply_rope(x, pos, 1e4)
    b = apply_mrope(x, pos3, 1e4, (2, 3, 3))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                               atol=1e-6)


def test_param_counts_match_published():
    """Full configs land near the published parameter counts."""
    expected = {
        "qwen2-72b": 72e9, "qwen2-7b": 7.6e9, "granite-8b": 8e9,
        "deepseek-67b": 67e9, "rwkv6-7b": 7.5e9,
        "llama4-maverick-400b-a17b": 400e9,
    }
    for arch, want in expected.items():
        got = get_config(arch).param_count()
        assert 0.8 * want < got < 1.25 * want, (arch, got, want)
