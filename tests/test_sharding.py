"""Sharding-rule unit tests + an end-to-end mini dry-run on 8 host devices.

The mini dry-run executes in a subprocess (jax locks the device count at
first init, and the main test process must keep seeing 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_spec_rules_tp_and_fsdp():
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import _spec_for

    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    m = FakeMesh()
    key = lambda *names: tuple(
        type("K", (), {"key": n})() for n in names
    )
    # attention projection: TP on output dim, FSDP on input dim
    assert _spec_for(key("blocks", "sub0", "attn", "wq"),
                     (28, 4096, 8192), m) == P(None, "data", "model")
    # row-parallel output projection
    assert _spec_for(key("blocks", "sub0", "attn", "wo"),
                     (28, 8192, 4096), m) == P(None, "model", "data")
    # embedding: vocab-parallel
    assert _spec_for(key("embed",), (152064, 8192), m) == P("model", "data")
    # norm scales replicated
    assert _spec_for(key("blocks", "ln1", "scale"), (28, 4096), m) == P(
        None, None
    )
    # indivisible vocab degrades gracefully (49155 % 16 != 0)
    spec = _spec_for(key("embed",), (49155, 1024), m)
    assert spec[0] is None
    # moe experts: expert-parallel
    assert _spec_for(key("blocks", "sub0", "mlp", "w_up"),
                     (24, 32, 1024, 512), m)[1] == "model"
    # serving: no fsdp
    assert _spec_for(key("blocks", "sub0", "attn", "wq"),
                     (28, 4096, 8192), m, use_fsdp=False) == P(
        None, None, "model"
    )
    # 2-D serve view
    assert _spec_for(key("blocks", "sub0", "attn", "wk"),
                     (28, 4096, 512), m2d := type("M", (), {
                         "shape": {"data": 16, "kv": 4, "hd": 4},
                         "axis_names": ("data", "kv", "hd")})(),
                     use_fsdp=False, model_axes=("kv", "hd")) == P(
        None, None, ("kv", "hd")
    )


def test_skip_reasons():
    from repro.launch.specs import skip_reason

    assert skip_reason("qwen2-72b", "long_500k") is not None
    assert skip_reason("rwkv6-7b", "long_500k") is None
    assert skip_reason("recurrentgemma-9b", "long_500k") is None
    assert skip_reason("qwen2-72b", "train_4k") is None


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.sharding import (batch_shardings, make_shard_hook,
                                       opt_shardings, param_shardings)
    from repro.models import build_model
    from repro.optim import AdamWConfig, adamw_init
    from repro.train import make_train_step

    mesh = make_host_mesh(4, 2)
    cfg = get_config("{arch}", reduced=True)
    model = build_model(cfg, remat=True, shard=make_shard_hook(mesh))
    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    opt_shape = jax.eval_shape(adamw_init, params_shape)
    b, s = 8, 16
    batch = {{
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
    }}
    step = make_train_step(model, AdamWConfig(), donate=True)
    with jax.set_mesh(mesh):
        fn = jax.jit(step.__wrapped__,
                     in_shardings=(param_shardings(params_shape, mesh),
                                   opt_shardings(params_shape, mesh),
                                   batch_shardings(batch, mesh)),
                     donate_argnums=(0, 1))
        compiled = fn.lower(params_shape, opt_shape, batch).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # older jax: one dict per device
        cost = cost[0]
    print(json.dumps({{"flops": cost.get("flops", 0.0), "ok": True}}))
""")


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["granite-8b", "granite-moe-1b-a400m",
                                  "rwkv6-7b"])
def test_mini_dryrun_compiles_on_8_devices(arch):
    """lower+compile of the sharded train step on a 4x2 host mesh."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", MINI_DRYRUN.format(arch=arch)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["ok"] and result["flops"] > 0


ELASTIC_RESHARD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, tempfile
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import checkpoint as ckpt
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.sharding import param_shardings
    from repro.models import build_model

    cfg = get_config("granite-8b", reduced=True)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))

    mesh_a, mesh_b = make_host_mesh(4, 2), make_host_mesh(2, 4)   # elastic: 4x2 -> 2x4
    sh_a = param_shardings(params, mesh_a)
    sh_b = param_shardings(params, mesh_b)
    p_a = jax.tree.map(jax.device_put, params, sh_a)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 3, p_a)
        # restore ONTO THE OTHER MESH (reshard-on-load)
        p_b = ckpt.restore(d, 3, jax.eval_shape(lambda: params), sh_b)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # restored leaves actually carry the target sharding
        lb = jax.tree.leaves(p_b)[1]
        assert len(lb.sharding.device_set) in (1, 2, 4, 8)
    print(json.dumps({"ok": True}))
""")


@pytest.mark.slow
def test_elastic_reshard_on_load():
    """A checkpoint written on a 4x2 mesh restores onto a 2x4 mesh with
    identical values and target shardings (the elastic-scaling path)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", ELASTIC_RESHARD],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"]
