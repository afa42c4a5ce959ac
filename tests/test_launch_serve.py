"""The serve entry point's own surface: the published-width config,
replica placement, the compile-cache helper, and ``chip_smoke.py``'s
refusal to run without a TPU.

Whatever needs its own JAX start-up (a device count, the compile-cache
environment, the smoke script) runs in a subprocess, so this process
keeps one device and JAX's default config."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.configs import get_config
from repro.launch import serve

REPO = pathlib.Path(__file__).resolve().parent.parent
SRC = str(REPO / "src")


def _run(code_or_argv, *, env_extra=None, timeout=300):
    argv = ([sys.executable, "-c", code_or_argv]
            if isinstance(code_or_argv, str) else code_or_argv)
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra or {})
    return subprocess.run(argv, capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=REPO)


def test_model_config_published_widths_cut_in_depth():
    full = get_config("qwen2-7b")
    cut = serve.model_config("qwen2-7b", 8)
    assert cut.num_layers == 8
    for field in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
                  "vocab_size", "param_dtype"):
        assert getattr(cut, field) == getattr(full, field), field
    assert serve.model_config("qwen2-7b", None) == get_config(
        "qwen2-7b", reduced=True)
    for bad in (0, full.num_layers + 1):
        with pytest.raises(ValueError):
            serve.model_config("qwen2-7b", bad)


def test_chip_smoke_fails_without_tpu():
    out = _run([sys.executable, str(REPO / "chip_smoke.py")])
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "JAX found no TPU" in out.stderr


COMPILE_CACHE = textwrap.dedent("""
    import json, os
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache
    path = enable_compile_cache()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
    print(json.dumps({"path": path, "config": jax.config.jax_compilation_cache_dir,
                      "default": str(DEFAULT_CACHE_DIR)}))
""")


@pytest.mark.slow
@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir(tmp_path, env_set):
    """With JAX_COMPILATION_CACHE_DIR set the cache is written there and no
    other directory is configured; without it the fixed in-checkout path
    is configured (nothing is compiled in that case, so nothing is
    written into the checkout)."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_set else {}
    out = _run(COMPILE_CACHE, env_extra=env)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    want = str(tmp_path) if env_set else got["default"]
    assert got["path"] == got["config"] == want
    assert pathlib.Path(got["default"]) == REPO / ".jax_cache"
    if env_set:
        assert any(tmp_path.iterdir())


REPLICAS_ON_DEVICES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import argparse, json
    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--requests", "4", "--prompt-len", "12", "--max-new-tokens", "4",
         "--replicas", "4", "--route-policy", "round_robin"])
    cfg, model, params = serve.build_model_and_params(args)

    def run(a):
        front, engines, _ = serve.build_front(
            model, params, serve.serve_config(a), a)
        rids = serve.submit_workload(a, cfg, front, engines)
        results = front.drain()
        placements = [sorted(d.id for d in e.kv.k_pools.sharding.device_set)
                      for e in engines]
        for e in engines:
            e.close()
        return [list(results[r].tokens) for r in rids], placements

    fleet, placements = run(args)
    alone, _ = run(argparse.Namespace(**{**vars(args), "replicas": 1}))
    print(json.dumps({"placements": placements, "same": fleet == alone}))
""")


@pytest.mark.slow
def test_router_replicas_one_per_device():
    """``--replicas 4`` on a four-device host puts one replica on each
    device, and the fleet's greedy tokens equal the lone engine's."""
    out = _run(REPLICAS_ON_DEVICES, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["placements"] == [[0], [1], [2], [3]]
    assert got["same"]
