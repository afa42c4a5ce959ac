"""Replica sweep: ReplicaRouter over N Engines vs the N=1 plain engine.

Runs the SAME workload through the plain single-replica engine and through
a :class:`ReplicaRouter` over N in {1, 2, 4} independent Engine replicas
(least-loaded-pages placement, one KV pool + page table each) and reports,
per N:

  * token identity per request vs the N=1 reference — the router's
    correctness contract (placement must be semantically invisible; greedy
    decoding is per-sequence, so replica count cannot change a stream);
  * done-status permutation vs the reference;
  * global-accounting consistency: the router's merged page/counter view
    must equal the sum of the per-replica views
    (``ReplicaRouter.check_invariants``);
  * the amortization counters per decoded token (host syncs, ptab syncs)
    and the mean fused horizon, summed across replicas — deterministic
    scheduler events, which is what ``scripts/bench_regress.py`` gates on
    (never wall tok/s: shared-CPU wall clock swings 5x between runs).

Pools are roomy per replica: the identity claim requires staying off the
degraded growth-stall path (scratch-routed decode writes are the one
intentional stream divergence); admission still queues behind
``max_batch``, so placement, cross-replica admission and horizon
collapse/reopen all fire.

``benchmarks/run.py --only router`` gates on token identity + accounting
identity and appends the metrics to ``BENCH_serve.json`` (section
``router``).
"""

from __future__ import annotations

import copy
import time

# same workload generator / jit-cache warmer as the seed-vs-split bench
from benchmarks.bench_serve_throughput import _warm, _workload


def run() -> tuple[list[str], dict]:
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import Engine, ReplicaRouter, ServeConfig

    cfg = get_config("qwen2-7b", reduced=True)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    serve_cfg = ServeConfig(page_size=4, num_pages=64, max_pages_per_seq=32,
                            max_batch=3)
    reqs = _workload(cfg, n=8, seed=4, max_new=12)
    _warm(Engine, model, params, cfg, serve_cfg)

    ref = Engine(model, params, serve_cfg)
    for r in reqs:
        ref.submit(copy.deepcopy(r))
    ref_done = ref.run()
    ref_out = {i: [int(x) for x in ref_done[i].output] for i in ref_done}
    ref_statuses = sorted((i, r.status) for i, r in ref_done.items())

    sweep: dict[str, dict] = {}
    all_identical = True
    accounting_ok = True
    for n in (1, 2, 4):
        engines = [Engine(model, params, serve_cfg) for _ in range(n)]
        router = ReplicaRouter(
            [eng.as_replica(i) for i, eng in enumerate(engines)]
        )
        for r in reqs:
            router.submit(copy.deepcopy(r))
        t0 = time.perf_counter()
        done = router.run()
        wall = time.perf_counter() - t0
        out = {i: [int(x) for x in done[i].output] for i in done}
        token_identical = out == ref_out
        permuted_ok = (sorted((i, r.status) for i, r in done.items())
                       == ref_statuses)
        all_identical &= token_identical and permuted_ok
        try:
            router.check_invariants()
        except AssertionError as e:
            accounting_ok = False
            print(f"FAIL (N={n} accounting): {e}")
        total = router.global_counters()
        toks = total["decode_tokens"]
        decode_s = sum(eng.counters.seconds("serve.exec.decode")
                       for eng in engines)
        sweep[str(n)] = dict(
            wall=wall,
            decode_tokens=int(toks),
            decode_tok_per_s=toks / max(decode_s, 1e-9),
            host_syncs_per_tok=total["host_syncs"] / max(toks, 1),
            ptab_syncs_per_tok=total["ptab_syncs"] / max(toks, 1),
            mean_horizon=(total["decode_horizon"]
                          / max(total["decode_dispatches"], 1)),
            placements=[
                router.counters.get(f"placements_replica{i}")
                for i in range(n)
            ],
            token_identical=bool(token_identical),
        )
        s = sweep[str(n)]
        print(f"N={n}: {s['decode_tok_per_s']:.1f} decode tok/s (summed), "
              f"{s['host_syncs_per_tok']:.3f} host syncs/tok, "
              f"{s['ptab_syncs_per_tok']:.3f} ptab syncs/tok, "
              f"mean horizon {s['mean_horizon']:.2f}, "
              f"placements {s['placements']}, "
              f"token-identical {token_identical}")

    print(f"replica sweep token-identical to N=1 reference (all N): "
          f"{all_identical}; global accounting == per-replica sums: "
          f"{accounting_ok}")
    metrics = {
        "token_identical": bool(all_identical),
        "accounting_identical": bool(accounting_ok),
        # the cross-PR regression pair (deterministic scheduler events,
        # N=2 run): see scripts/bench_regress.py
        "host_syncs_per_token": float(sweep["2"]["host_syncs_per_tok"]),
        "mean_horizon": float(sweep["2"]["mean_horizon"]),
        "sweep": sweep,
    }
    csv = [
        f"router_token_identical,0,{int(all_identical)}",
        f"router_accounting_identical,0,{int(accounting_ok)}",
        f"router_host_syncs_per_tok_n2,0,"
        f"{sweep['2']['host_syncs_per_tok']:.4f}",
        f"router_ptab_syncs_per_tok_n2,0,"
        f"{sweep['2']['ptab_syncs_per_tok']:.4f}",
        f"router_mean_horizon_n2,0,{sweep['2']['mean_horizon']:.2f}",
        f"router_decode_tok_per_s_n4,0,"
        f"{sweep['4']['decode_tok_per_s']:.2f}",
    ]
    return csv, metrics


def main() -> list[str]:
    csv, _ = run()
    return csv


if __name__ == "__main__":
    main()
