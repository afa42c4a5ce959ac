"""Sharded executor over the ('kv', 'hd') serve mesh: kernels LIVE.

Before this PR the mesh mode silently swapped a kernel-built model for
its jnp twin (every Pallas kernel assumed the full single-device pool
view), so sharded serving forfeited the paged-prefill kernel's
bytes-gathered win.  Now the executor wraps the kernels in shard_map and
dispatches them on each device's local pool slice, and this benchmark is
the gate that keeps them live.

It preloads a shared prefix and drives a forked-prefix workload (COW
tail-page copies + batched continuation prefill — the dispatch whose
gather volume the PR 2 kernel collapsed) through THREE engines built
from the same kernel model:

  * ``single``      — no mesh, Pallas kernels;
  * ``sharded``     — >1-device mesh, Pallas kernels through shard_map;
  * ``sharded_ref`` — same mesh, ``ServeConfig.use_ref_path=True``: the
    explicit jnp escape hatch (``--no-kernels``), kept as the baseline
    that shows what the mesh used to cost.

Reported per engine: decode tok/s (informational on CPU-forced host
devices), kernel vs ref-path dispatch counts, and ``prefill_bytes_gathered``
— the modeled KV bytes the continuation-prefill attention reads (kernel:
only pages the banded [start, start+chunk) window touches; ref path: every
``max_pages_per_seq`` page of every row).  ``benchmarks/run.py --only
sharded`` gates on token identity single vs sharded, kernels actually live
(``kernel_dispatches > 0`` and ``ref_path_dispatches == 0``), and the
sharded engine gathering STRICTLY fewer prefill bytes than the ref-path
engine; wall-clock is never gated (CPU collectives are emulation).

With a single visible device the mesh degrades to 1x1 and the
``sharded`` engine still runs the shard_map-free kernel path; the CI
``multidevice`` job forces 8 host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

# same driver and jit-cache warmer as the seed-vs-split benchmark
from benchmarks.bench_serve_throughput import _drive, _warm


def _fork_workload(cfg, n=5, seed=17, max_new=10):
    from repro.serve import ServeRequest

    rng = np.random.default_rng(seed)
    return [
        ServeRequest(req_id=i,
                     prompt=rng.integers(0, cfg.vocab_size,
                                         size=int(rng.integers(4, 10))
                                         ).astype(np.int32),
                     max_new_tokens=max_new, share_prefix=True)
        for i in range(n)
    ]


def run() -> tuple[list[str], dict]:
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_host_serve_mesh
    from repro.models import build_model
    from repro.serve import Engine, ServeConfig

    cfg = get_config("qwen2-7b", reduced=True)
    model = build_model(cfg, remat=False, use_kernels=True)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_host_serve_mesh(cfg.num_kv_heads, cfg.head_dim)
    print(f"serve mesh {dict(mesh.shape)}: {mesh.size} of "
          f"{jax.device_count()} visible devices")

    serve_cfg = ServeConfig(page_size=4, num_pages=32, max_pages_per_seq=16,
                            max_batch=3)
    prefix = np.random.default_rng(5).integers(
        0, cfg.vocab_size, size=10).astype(np.int32)
    reqs = _fork_workload(cfg)

    plans = (
        ("single", {}, False),
        ("sharded", {"mesh": mesh}, False),
        ("sharded_ref", {"mesh": mesh}, True),
    )
    results = {}
    outs = {}
    for name, kw, ref_path in plans:
        scfg = dataclasses.replace(serve_cfg, use_ref_path=ref_path)
        _warm(functools.partial(Engine, **kw), model, params, cfg, scfg)
        eng = Engine(model, params, scfg, **kw)
        eng.preload_prefix(prefix)
        done, wall = _drive(eng, reqs)
        eng.executor.check_sharding_invariants()
        outs[name] = {i: [int(x) for x in done[i].output] for i in done}
        c = eng.counters
        toks = c.get("decode_tokens")
        results[name] = dict(
            wall=wall,
            decode_tok_per_s=toks / max(c.seconds("serve.exec.decode"), 1e-9),
            host_syncs_per_tok=c.ratio("host_syncs", "decode_tokens"),
            ptab_syncs_per_tok=c.ratio("ptab_syncs", "decode_tokens"),
            mean_horizon=(c.get("decode_horizon")
                          / max(c.get("decode_dispatches"), 1)),
            forked_admissions=c.get("forked_admissions"),
            kernel_dispatches=c.get("kernel_dispatches"),
            ref_path_dispatches=c.get("ref_path_dispatches"),
            prefill_bytes_gathered=c.get("prefill_bytes_gathered"),
        )
        r = results[name]
        print(f"{name:>11}: {r['decode_tok_per_s']:.1f} decode tok/s, "
              f"{r['kernel_dispatches']} kernel / "
              f"{r['ref_path_dispatches']} ref-path dispatches, "
              f"{r['forked_admissions']} forked admissions, "
              f"{r['prefill_bytes_gathered']} B prefill KV gathered")

    single, shard, ref = (results["single"], results["sharded"],
                          results["sharded_ref"])
    token_identical = outs["single"] == outs["sharded"]
    counters_identical = all(
        single[k] == shard[k]
        for k in ("host_syncs_per_tok", "ptab_syncs_per_tok", "mean_horizon",
                  "forked_admissions", "kernel_dispatches",
                  "prefill_bytes_gathered")
    )
    kernels_live = (shard["kernel_dispatches"] > 0
                    and shard["ref_path_dispatches"] == 0
                    and single["ref_path_dispatches"] == 0)
    bytes_win = (shard["prefill_bytes_gathered"]
                 < ref["prefill_bytes_gathered"]
                 if shard["forked_admissions"] > 0 else False)
    ratio = (ref["prefill_bytes_gathered"]
             / max(shard["prefill_bytes_gathered"], 1))
    print(f"sharded outputs token-identical to single-device kernels: "
          f"{token_identical}; counters identical: {counters_identical}")
    print(f"kernels live on the mesh: {kernels_live}; prefill KV gather "
          f"kernel vs ref path: {shard['prefill_bytes_gathered']} B vs "
          f"{ref['prefill_bytes_gathered']} B ({ratio:.2f}x fewer)")

    metrics = {
        "mesh_devices": int(mesh.size),
        "visible_devices": int(jax.device_count()),
        "token_identical": bool(token_identical),
        "counters_identical": bool(counters_identical),
        "kernels_live": bool(kernels_live),
        "bytes_win": bool(bytes_win),
        "prefill_bytes_gathered_kernel": int(shard["prefill_bytes_gathered"]),
        "prefill_bytes_gathered_ref": int(ref["prefill_bytes_gathered"]),
        "ref_path_dispatches": int(shard["ref_path_dispatches"]),
        "kernel_dispatches": int(shard["kernel_dispatches"]),
        "single": single,
        "sharded": shard,
        "sharded_ref": ref,
    }
    csv = [
        f"serve_sharded_mesh_devices,0,{mesh.size}",
        f"serve_sharded_token_identical,0,{int(token_identical)}",
        f"serve_sharded_kernels_live,0,{int(kernels_live)}",
        f"serve_sharded_kernel_dispatches,0,{shard['kernel_dispatches']}",
        f"serve_sharded_ref_path_dispatches,0,"
        f"{shard['ref_path_dispatches']}",
        f"serve_sharded_prefill_bytes_gathered,0,"
        f"{shard['prefill_bytes_gathered']}",
        f"serve_sharded_prefill_bytes_gathered_ref,0,"
        f"{ref['prefill_bytes_gathered']}",
        f"serve_sharded_decode_tok_per_s,0,"
        f"{shard['decode_tok_per_s']:.2f}",
    ]
    return csv, metrics


def main() -> list[str]:
    csv, _ = run()
    return csv


if __name__ == "__main__":
    main()
