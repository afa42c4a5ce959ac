"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Spins up the split serving engine (host Scheduler = policy plane, device
Executor = data plane; see ``repro/serve/engine.py``) and reports the
paper-aligned statistics: translation bursts, page faults, context-switch
bytes/cycles, page-table delta uploads, tokens/s.

By default it builds the reduced preset of ``--arch`` (CPU-sized).
``--layers N`` builds the published widths instead, with the depth cut to
N whole layers: the configuration a one-chip deployment serves.

All serving flags come from ``ServeConfig.add_args`` — the single flag
surface shared with the benchmarks — and the config header is
``ServeConfig.describe()``.  This driver adds only model depth
(--layers), workload shape (--requests/--prompt-len/...) and fleet shape
(--replicas/--route-policy).  ``chip_smoke.py`` builds its engines through
the functions below, so the smoke and this driver share one code path.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.kernels.common import should_interpret
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_device_mesh
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.serve import Engine, ServeConfig, ServeRequest


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen2-7b")
    ap.add_argument("--layers", type=int, default=None,
                    help="serve the published widths with the depth cut "
                         "to this many whole layers (default: the reduced "
                         "CPU preset)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=24)
    ap.add_argument("--prompt-len", type=int, default=24,
                    help="prompt lengths are drawn from "
                         "[prompt_len // 2, prompt_len]")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="preload a shared prefix; requests fork from it "
                         "(continuation prefill through the Executor)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="model replicas behind the ReplicaRouter: N "
                         "independent Scheduler+Executor pairs (each with "
                         "its own KV pools / page table) fed from one "
                         "global admission queue; 1 = the plain engine.  "
                         "Without a serve mesh, replica i is placed on "
                         "device i mod device_count")
    ap.add_argument("--route-policy", default="least_loaded",
                    choices=("least_loaded", "round_robin"),
                    help="replica placement policy (fork affinity is "
                         "always enforced on top: COW forks stay on a "
                         "prefix-holding replica)")
    ap.add_argument("--stream", action="store_true",
                    help="attach a per-request stream callback: tokens are "
                         "detokenized and delivered by the background "
                         "AsyncDetokenizer thread in commit order")
    ServeConfig.add_args(ap)
    return ap


def model_config(arch: str, layers: int | None) -> ModelConfig:
    """The reduced preset of ``arch``, or (with ``layers``) its published
    widths with the depth cut to ``layers`` whole layers."""
    if layers is None:
        return get_config(arch, reduced=True)
    cfg = get_config(arch)
    if not 1 <= layers <= cfg.num_layers:
        raise ValueError(
            f"--layers must be in [1, {cfg.num_layers}] for {arch}, "
            f"got {layers}")
    return dataclasses.replace(cfg, num_layers=layers)


def build_model_and_params(args) -> tuple[ModelConfig, object, object]:
    """``(model_cfg, model, params)`` with weights drawn from ``--seed``.

    Kernels are the serving path everywhere (single device AND mesh);
    ``--no-kernels`` flips the executor onto the jnp twin instead of
    rebuilding a kernel-free model, so the hatch is visible in counters.
    The init is jitted so the weights are drawn on the device.
    """
    cfg = model_config(args.arch, args.layers)
    if cfg.family in ("rwkv6", "hybrid_rglru"):
        raise SystemExit(
            f"{args.arch}: engine drives paged-KV transformers; recurrent "
            "families decode via model.decode_step (see examples/)"
        )
    model = build_model(cfg, remat=False, use_kernels=True)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    return cfg, model, params


def serve_config(args, **overrides) -> ServeConfig:
    """The validated :class:`ServeConfig`, with the page-table reach sized
    to the workload (prefix + longest prompt + new tokens)."""
    reach = (args.prefix_len + args.prompt_len + args.max_new_tokens)
    overrides.setdefault(
        "max_pages_per_seq", max(4, reach // args.page_size + 2))
    return ServeConfig.from_args(args, **overrides)


def replica_meshes(serve_cfg: ServeConfig, model_cfg: ModelConfig,
                   replicas: int) -> list:
    """One placement per replica.

    With a serve mesh every replica shards over it.  Without one, a lone
    replica keeps the plain single-device path, and N > 1 replicas each
    get a 1x1 ('kv', 'hd') mesh on device ``i mod device_count`` — one
    replica per chip on a multi-chip host.
    """
    mesh = serve_cfg.build_mesh(model_cfg)
    if mesh is not None or replicas == 1:
        return [mesh] * replicas
    devices = jax.devices()
    return [make_device_mesh(devices[i % len(devices)])
            for i in range(replicas)]


def build_front(model, params, serve_cfg: ServeConfig, args):
    """``(front, engines, router)``: the engines of the fleet and the
    object requests are submitted to (the router when there are several
    replicas, else the one engine)."""
    replicas = max(1, args.replicas)
    meshes = replica_meshes(serve_cfg, model.cfg, replicas)
    engines = [Engine(model, params, serve_cfg, mesh=m) for m in meshes]
    router = None
    if replicas > 1:
        from repro.serve import ReplicaRouter
        router = ReplicaRouter(
            [e.as_replica(i) for i, e in enumerate(engines)],
            policy=args.route_policy,
        )
    front = router if router is not None else engines[0]
    return front, engines, router


def workload(args, model_cfg: ModelConfig) -> tuple[np.ndarray | None,
                                                   list[np.ndarray]]:
    """``(prefix, prompts)`` drawn from ``--seed``: the same requests on
    every call with the same arguments."""
    rng = np.random.default_rng(args.seed)
    prefix = None
    if args.prefix_len > 0:
        prefix = rng.integers(0, model_cfg.vocab_size,
                              size=args.prefix_len).astype(np.int32)
    prompts = []
    for _ in range(args.requests):
        plen = int(rng.integers(args.prompt_len // 2, args.prompt_len + 1))
        shape = (plen, model_cfg.num_codebooks) if (
            model_cfg.family == "audio" and model_cfg.num_codebooks > 1
        ) else (plen,)
        prompts.append(rng.integers(0, model_cfg.vocab_size,
                                    size=shape).astype(np.int32))
    return prefix, prompts


def submit_workload(args, model_cfg: ModelConfig, front, engines,
                    stream_callback=None) -> list[int]:
    """Preload the shared prefix on every replica (each can parent COW
    forks) and submit the workload; returns the request ids in order."""
    prefix, prompts = workload(args, model_cfg)
    if prefix is not None:
        for e in engines:
            e.preload_prefix(prefix)
    return [front.submit(ServeRequest(
        prompt=p,
        max_new_tokens=args.max_new_tokens,
        share_prefix=prefix is not None,
        stream_callback=stream_callback,
    )) for p in prompts]


def device_label() -> str:
    """The device the kernels run on, as JAX names it, and how."""
    d = jax.devices()[0]
    mode = "Pallas interpret mode" if should_interpret() else "compiled"
    return f"{d.platform} {d.device_kind} x{jax.device_count()}, {mode}"


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg, model, params = build_model_and_params(args)
    serve_cfg = serve_config(args)
    print(serve_cfg.describe())
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model}"
          f" heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim="
          f"{cfg.head_dim} vocab={cfg.vocab_size}; device: {device_label()}")
    front, engines, router = build_front(model, params, serve_cfg, args)
    eng = engines[0]
    if serve_cfg.serve_mesh != "off":
        mesh = eng.executor.mesh
        print(f"serve mesh: {dict(mesh.shape)} over {mesh.size} of "
              f"{jax.device_count()} visible devices (KV pools sharded, "
              "page table replicated)")
    if router is not None:
        print(f"replica router: {args.replicas} replicas "
              f"({args.route_policy}; each {args.num_pages} frames, "
              f"max_batch {args.max_batch})")
    streamed: list = []
    submit_workload(args, cfg, front, engines,
                    stream_callback=streamed.append if args.stream else None)
    t0 = time.perf_counter()
    results = front.drain()
    dt = time.perf_counter() - t0
    stats = eng.stats()
    total_tokens = sum(len(r.tokens) for r in results.values())
    n_done = sum(1 for r in results.values() if r.status == "done")
    n_failed = sum(1 for r in results.values() if r.status == "failed")
    print(f"completed {n_done}/{args.requests} requests "
          f"({n_failed} failed reach checks), "
          f"{total_tokens} tokens in {dt:.1f}s "
          f"({total_tokens / dt:.1f} tok/s on {device_label()}, "
          "compilation included)")
    finished = [r for r in results.values() if r.status == "done"]
    if finished:
        ttfts = sorted(r.ttft for r in finished)
        tpots = sorted(r.tpot for r in finished)
        mid = len(finished) // 2
        print(f"  latency: TTFT p50 {ttfts[mid] * 1e3:.1f} ms / "
              f"max {ttfts[-1] * 1e3:.1f} ms, TPOT p50 "
              f"{tpots[mid] * 1e3:.1f} ms (commit-point stamps), peak "
              f"{max(r.pages_peak for r in finished)} pages/request")
    if args.stream:
        print(f"  streamed {len(streamed)} events via AsyncDetokenizer "
              f"(backlog peak {eng.counters.get('detok_backlog_peak')})")
    if router is not None:
        r = router.counters
        print(f"router: {r.get('placements')} placements "
              f"({', '.join(str(r.get(f'placements_replica{i}')) for i in range(args.replicas))} per replica), "
              f"{r.get('migrations_declined')} migrations declined, "
              f"{r.get('cross_replica_queue_waits')} queue-wait steps")
        print("router global counters:", dict(router.global_counters()))
        print("router global pages:", router.global_page_report())
        router.check_invariants()
        print("-- replica 0 detail --")
    print("scheduler (policy plane) counters:", stats["counters"])
    print("executor (data plane): context switches:", stats["switch_stats"])
    print(f"  page-table delta uploads: "
          f"{stats['counters'].get('ptab_rows_uploaded', 0)} rows in "
          f"{stats['counters'].get('ptab_syncs', 0)} syncs over "
          f"{eng.scheduler.step_i} steps "
          f"(seed engine: {eng.scheduler.step_i * eng.cfg.max_batch} rows)")
    c = eng.counters
    print(f"  kernel dispatch: {c.get('kernel_dispatches')} kernel / "
          f"{c.get('ref_path_dispatches')} ref-path compute steps, "
          f"{c.get('prefill_bytes_gathered')} B continuation-prefill KV "
          f"gathered")
    if serve_cfg.aot_buckets:
        print(f"  aot prefill: {c.get('aot_hits')} hits / "
              f"{c.get('aot_misses')} misses, "
              f"{c.get('bucket_pad_tokens')} pad tokens")
    kp, vp = eng.kv.k_pools, eng.kv.v_pools
    per_page = (int(kp.nbytes) + int(vp.nbytes)) // kp.shape[1]
    print(f"  kv pools: dtype={kp.dtype} ({args.kv_dtype}), "
          f"{per_page} B/page across {kp.shape[1]} frames, "
          f"{c.get('quant_dispatches')} quantized compute steps")
    print(f"  fused decode horizon: mean "
          f"{c.get('decode_horizon') / max(c.get('decode_dispatches'), 1):.2f}"
          f" over {c.get('decode_dispatches')} dispatches, "
          f"{c.ratio('host_syncs', 'decode_tokens'):.3f} host syncs/token, "
          f"{c.get('horizon_collapses')} pool-pressure collapses")
    print(f"  radix prefix cache: {c.get('prefix_hits')} hits, "
          f"{c.get('pages_reused')} pages reused, "
          f"{c.get('prefill_tokens_skipped')} prefill tokens skipped, "
          f"{c.get('shared_restores')} shared restores")
    print("pool:", stats["pool"])
    for e in engines:
        e.close()


if __name__ == "__main__":
    main()
