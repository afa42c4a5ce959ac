"""Serving split before/after + fused decode-horizon sweep.

Section 1 (seed vs split) runs the same preempting workload through the
frozen seed engine (``repro.serve.reference.ReferenceEngine``, monolithic
host loop: full page-table re-upload each step, full-pool stack+reshape
per spill/restore) and the refactored Scheduler/Executor engine
(persistent delta-updated device page table, donated jitted steps,
page-granular spill, fused multi-step decode), and reports:

  * decode tokens/s (wall; CPU-interpret numbers — the *ratio* is the
    signal, absolute rates are hardware-dependent; the executor's timers
    ``block_until_ready`` the step outputs, so they measure execution,
    not async dispatch);
  * spill/restore bytes actually moved per context switch.  The seed's
    *counter* already counted victim pages only, so its data-plane
    pathology is reported separately as ``touched`` bytes: every seed
    spill stacks both full pools (2 x pool bytes) and every restore
    rebuilds them (2 x more), regardless of victim size;
  * page-table rows uploaded to the device per decode step (seed: all
    ``max_batch`` rows, every step).

Section 2 (horizon sweep) runs the split engine with the fused decode
horizon forced to K=1 vs auto, reporting decode tokens/s, host syncs per
decoded token (forced device->host transfers — the scalar-plane
interventions the horizon amortizes) and page-table delta syncs per
token.  ``benchmarks/run.py --only serve`` gates on the auto-horizon
numbers: greedy outputs must stay token-identical to the seed engine and
``host_syncs / decode_tokens`` must be strictly below 1.0.
"""

from __future__ import annotations

import copy
import dataclasses
import time

import numpy as np


def _workload(cfg, n=6, seed=0, max_new=12):
    from repro.serve import ServeRequest

    rng = np.random.default_rng(seed)
    return [
        ServeRequest(req_id=i,
                     prompt=rng.integers(0, cfg.vocab_size,
                                         size=int(rng.integers(6, 16))
                                         ).astype(np.int32),
                     max_new_tokens=max_new)
        for i in range(n)
    ]


def _drive(eng, reqs):
    from repro.serve import ReferenceEngine
    from repro.serve.api import to_internal

    for r in reqs:
        r = copy.deepcopy(r)
        # the frozen seed engine predates the typed client surface: lower
        # explicitly; the split engine takes the ServeRequest itself
        eng.submit(to_internal(r) if isinstance(eng, ReferenceEngine)
                   else r)
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    return done, wall


def _warm(eng_cls, model, params, cfg, serve_cfg):
    """Compile every graph the timed run can hit before timing it.

    ``max_new=12`` walks the auto-horizon ladder through K=8, 2, 1 and
    ``max_new=6`` through K=4, 1, so all power-of-two fused-decode
    variants (plus the prefill shapes) are in the jit cache — otherwise
    their compile time would land inside the timed decode region."""
    for max_new in (12, 6):
        _drive(eng_cls(model, params, serve_cfg),
               _workload(cfg, n=2, seed=1, max_new=max_new))


def run() -> tuple[list[str], dict]:
    import jax  # noqa: F401  (device init before timing)

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import Engine, ReferenceEngine, ServeConfig

    cfg = get_config("qwen2-7b", reduced=True)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    serve_cfg = ServeConfig(page_size=4, num_pages=16, max_pages_per_seq=16,
                            max_batch=3)
    reqs = _workload(cfg)

    results = {}
    outs = {}
    for name, eng_cls in (("seed", ReferenceEngine), ("split", Engine)):
        # warm the jit caches so the timed run measures steady-state decode
        _warm(eng_cls, model, params, cfg, serve_cfg)
        eng = eng_cls(model, params, serve_cfg)
        done, wall = _drive(eng, reqs)
        outs[name] = {i: [int(x) for x in done[i].output] for i in done}
        c = eng.counters
        steps = c.get("decode_tokens")
        st = eng.switcher.stats
        kp = eng.kv.k_pools
        n_layers, n_frames, page, hkv, hd = kp.shape
        per_page = n_layers * page * hkv * hd * kp.dtype.itemsize
        pool_bytes = n_frames * per_page
        if name == "seed":
            # data plane actually touched: jnp.stack of BOTH full pools on
            # every spill and every restore, plus the full-pool rebuild
            # after the restore scatter (2x pool each time)
            touched = (st.switches + c.get("restores")) * 2 * pool_bytes
            # full [max_batch, max_pages] table re-uploaded on every engine
            # step that decoded (upper-bounded by total steps)
            ptab_rows = eng._step_i * eng.cfg.max_batch
        else:
            touched = st.bytes_spilled + st.bytes_restored
            ptab_rows = c.get("ptab_rows_uploaded")
        decode_s = c.seconds("serve.exec.decode") or wall
        results[name] = dict(
            wall=wall, tokens=sum(len(r.output) for r in done.values()),
            decode_steps=steps, decode_seconds=decode_s,
            switches=st.switches, moved=st.bytes_spilled + st.bytes_restored,
            touched=touched, ptab_rows=ptab_rows,
        )
        print(f"{name:>6}: {results[name]['tokens']} tokens in {wall:.1f}s, "
              f"{st.switches} switches, "
              f"{results[name]['moved']} B victim pages moved, "
              f"{touched} B pool bytes touched, "
              f"{ptab_rows} page-table rows uploaded")

    seed, split = results["seed"], results["split"]
    token_identical = outs["seed"] == outs["split"]
    rate_seed = seed["decode_steps"] / max(seed["decode_seconds"], 1e-9)
    rate_split = split["decode_steps"] / max(split["decode_seconds"], 1e-9)
    print(f"decode tokens/s: seed {rate_seed:.1f} -> split {rate_split:.1f} "
          f"({rate_split / max(rate_seed, 1e-9):.2f}x, CPU interpret)")
    print(f"bytes touched per switch: seed "
          f"{seed['touched'] // max(seed['switches'], 1)} -> split "
          f"{split['touched'] // max(split['switches'], 1)}")
    print(f"greedy outputs token-identical to seed at auto-horizon: "
          f"{token_identical}")

    # ---- horizon sweep: forced K=1 vs auto ---------------------------
    # a single admission wave in a roomy pool: the queue drains on step 1,
    # so the run isolates the steady-state decode loop the horizon fuses
    # (the contended seed-vs-split workload above keeps the horizon mostly
    # collapsed — by design; that is its identity stress)
    sweep_reqs = _workload(cfg, n=3, seed=2)
    sweep = {}
    for label, mh in (("h1", 1), ("auto", serve_cfg.max_horizon)):
        swp_cfg = dataclasses.replace(serve_cfg, num_pages=64,
                                      max_pages_per_seq=32, max_horizon=mh)
        _warm(Engine, model, params, cfg, swp_cfg)
        eng = Engine(model, params, swp_cfg)
        _drive(eng, sweep_reqs)
        c = eng.counters
        toks = c.get("decode_tokens")
        sweep[label] = dict(
            decode_tokens=toks,
            decode_tok_per_s=toks / max(c.seconds("serve.exec.decode"), 1e-9),
            host_syncs=c.get("host_syncs"),
            host_syncs_per_tok=c.ratio("host_syncs", "decode_tokens"),
            ptab_syncs=c.get("ptab_syncs"),
            ptab_syncs_per_tok=c.ratio("ptab_syncs", "decode_tokens"),
            dispatches=c.get("decode_dispatches"),
            mean_horizon=(c.get("decode_horizon")
                          / max(c.get("decode_dispatches"), 1)),
        )
        s = sweep[label]
        print(f"horizon {label:>4}: {s['decode_tok_per_s']:.1f} decode tok/s, "
              f"{s['host_syncs_per_tok']:.3f} host syncs/tok, "
              f"{s['ptab_syncs_per_tok']:.3f} ptab syncs/tok, "
              f"mean horizon {s['mean_horizon']:.2f} "
              f"({s['dispatches']} dispatches)")

    metrics = {
        "token_identical": bool(token_identical),
        "host_syncs_per_token": float(sweep["auto"]["host_syncs_per_tok"]),
        "mean_horizon": float(sweep["auto"]["mean_horizon"]),
        "decode_tok_per_s_seed": float(rate_seed),
        "decode_tok_per_s_split": float(rate_split),
        "ctx_bytes_touched_seed": int(seed["touched"]),
        "ctx_bytes_touched_split": int(split["touched"]),
        "sweep": sweep,
    }
    csv = [
        f"serve_decode_tok_per_s_seed,0,{rate_seed:.2f}",
        f"serve_decode_tok_per_s_split,0,{rate_split:.2f}",
        f"serve_ctx_bytes_touched_seed,0,{seed['touched']}",
        f"serve_ctx_bytes_touched_split,0,{split['touched']}",
        f"serve_ptab_rows_uploaded_seed,0,{seed['ptab_rows']}",
        f"serve_ptab_rows_uploaded_split,0,{split['ptab_rows']}",
        f"serve_decode_tok_per_s_h1,0,{sweep['h1']['decode_tok_per_s']:.2f}",
        f"serve_decode_tok_per_s_auto,0,"
        f"{sweep['auto']['decode_tok_per_s']:.2f}",
        f"serve_host_syncs_per_tok_h1,0,"
        f"{sweep['h1']['host_syncs_per_tok']:.4f}",
        f"serve_host_syncs_per_tok_auto,0,"
        f"{sweep['auto']['host_syncs_per_tok']:.4f}",
        f"serve_ptab_syncs_per_tok_auto,0,"
        f"{sweep['auto']['ptab_syncs_per_tok']:.4f}",
        f"serve_mean_horizon_auto,0,{sweep['auto']['mean_horizon']:.2f}",
    ]
    return csv, metrics


def main() -> list[str]:
    csv, _ = run()
    return csv


if __name__ == "__main__":
    main()
