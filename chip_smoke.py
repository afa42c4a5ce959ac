#!/usr/bin/env python3
"""Smoke test of the serving main path on a TPU, at published qwen2-7b widths.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the multi-chip paths, on four chips

One chip: builds qwen2-7b at its published widths (hidden 3584, 28/4
heads, head_dim 128, FFN 18944, vocab 152064) with the depth cut to 8
layers and random weights from ``--seed``, through the same functions as
``python -m repro.launch.serve --layers 8``.  It serves 8 prompts of
256-512 tokens, 32 new tokens each, greedy, auto horizon, and drains them:
once through the Pallas kernels and once through the jnp reference path
(``--no-kernels``), for a bf16 pool and for an int8 pool.  The kernel
engine's greedy tokens must equal the reference engine's, apart from
reported near-ties.

``--chips 4`` runs only what exists across chips, each compared token for
token with the one-chip engine on the same requests: (a) the
ReplicaRouter over four one-chip replicas, one per device, and (b) the
('kv', 'hd') serve mesh over four chips (kv=4, hd=1).

Everything runs in this one process.  The script fails (nonzero exit, no
result line) when JAX finds no TPU, when anything ran interpreted or on
the reference path in the engine under test, when a request fails, or on
a divergence that is not a near-tie.  The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.common import should_interpret  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import TransformerLM  # noqa: E402

LAYERS = 8
SERVE_ARGS = [
    "--arch", "qwen2-7b", "--layers", str(LAYERS),
    "--requests", "8", "--prompt-len", "512", "--max-new-tokens", "32",
    "--page-size", "16", "--num-pages", "4096", "--max-batch", "8",
    "--max-horizon", "8",
]
#: a divergence is a near-tie when the logits of both chosen tokens lie
#: within this many ulps of the model dtype (bf16 at published widths) of
#: the reference's top logit
NEAR_TIE_ULPS = 4


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from
    the persistent cache), from JAX's own monitoring events.  Nested jits
    report spans inside their caller's, so the spans are merged before
    they are summed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self._spans: list[tuple[float, float]] = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            end = time.perf_counter()       # reported as the span closes
            self._spans.append((end - secs, end))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def seconds(self) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self._spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total


def check(ok: bool, msg: str) -> None:
    if not ok:
        print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
        sys.exit(1)


def device_report() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> dict[int, int]:
    return {d.id: int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in jax.devices()}


def serve_once(name: str, args, model, params, clock: CompileClock,
               *, under_test: bool, **overrides):
    """Build a fleet through ``launch.serve``, drain the workload, and
    return ``(tokens by request order, counters, engines' placements)``."""
    serve_cfg = serve.serve_config(args, **overrides)
    front, engines, router = serve.build_front(model, params, serve_cfg, args)
    rids = serve.submit_workload(args, model.cfg, front, engines)
    c0, t0 = clock.seconds, time.perf_counter()
    results = front.drain()
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    counters = (router.global_counters() if router is not None
                else collections.Counter(engines[0].counters.counters))
    placements = [sorted(d.id for d in e.kv.k_pools.sharding.device_set)
                  for e in engines]
    pool = jax.ShapeDtypeStruct(engines[0].kv.k_pools.shape,
                                engines[0].kv.k_pools.dtype)
    pool_bytes = sum(int(e.kv.k_pools.nbytes) + int(e.kv.v_pools.nbytes)
                     for e in engines)
    n_engines = len(engines)
    for e in engines:
        e.close()
    del front, engines, router
    gc.collect()                  # free this fleet's pools before the next
    done = sum(1 for r in results.values() if r.status == "done")
    failed = len(results) - done
    print(f"[{name}] pool: {pool.shape[1]} pages x {pool.shape[2]} tokens, "
          f"dtype {pool.dtype}, {pool_bytes} B K+V over {n_engines} "
          f"engine(s) on devices {placements}")
    print(f"[{name}] completed {done}/{len(rids)} requests, {failed} failed; "
          f"wall {wall:.3f} s = compile {compile_s:.3f} s + run "
          f"{wall - compile_s:.3f} s")
    print(f"[{name}] kernel_dispatches {counters['kernel_dispatches']} "
          f"ref_path_dispatches {counters['ref_path_dispatches']} "
          f"quant_dispatches {counters['quant_dispatches']} "
          f"decode_dispatches {counters['decode_dispatches']} "
          f"decode_horizon {counters['decode_horizon']}")
    check(done == len(rids) and failed == 0,
          f"{name}: {failed} of {len(rids)} requests did not complete")
    if under_test:
        check(counters["ref_path_dispatches"] == 0
              and counters["kernel_dispatches"] > 0,
              f"{name}: compute steps did not all run through the kernels")
        if serve_cfg.kv_dtype == "int8":
            check(counters["quant_dispatches"] > 0,
                  f"{name}: no compute step ran over the int8 pool")
    tokens = [list(results[r].tokens) for r in rids]
    return tokens, counters, placements


def reference_logits(cfg, params, prompt, generated, *, kv_dtype: str,
                     page_size: int) -> np.ndarray:
    """Per-step logits of the jnp reference path for one request: prefill
    of the prompt, then one decode step per generated token.  Row ``t``
    are the logits that chose output token ``t``."""
    model = TransformerLM(cfg, remat=False, use_kernels=False,
                          kv_dtype=kv_dtype)
    n = len(prompt) + len(generated)
    pages = -(-(n + 1) // page_size)
    state = model.init_kv_state(1, pages + 1, page_size, pages)
    state = state._replace(
        page_table=jnp.arange(pages, dtype=jnp.int32)[None])
    logits, state = model.prefill(
        params, jnp.asarray(prompt)[None],
        jnp.asarray([len(prompt)], jnp.int32), state)
    rows = [logits[0]]
    for tok in generated[:-1]:
        logits, state = model.decode_step(
            params, jnp.asarray([tok], jnp.int32), state)
        rows.append(logits[0])
    return np.asarray(jnp.stack(rows).astype(jnp.float32))


def compare(name: str, got, want, args, cfg, params, *,
            kv_dtype: str) -> None:
    """Token-for-token comparison; the first divergence of each request
    is reported with the reference's top-2 logit margin, and fails unless
    it is a near-tie."""
    _, prompts = serve.workload(args, cfg)
    same = sum(g == w for g, w in zip(got, want))
    print(f"[{name}] {same}/{len(want)} requests token-identical")
    for i, (g, w) in enumerate(zip(got, want)):
        if g == w:
            continue
        t = next((j for j, (a, b) in enumerate(zip(g, w)) if a != b),
                 min(len(g), len(w)))
        check(t < min(len(g), len(w)),
              f"{name}: request {i} lengths differ ({len(g)} vs {len(w)})")
        logits = reference_logits(cfg, params, prompts[i], w[: t + 1],
                                  kv_dtype=kv_dtype,
                                  page_size=args.page_size)[t]
        top2 = np.argsort(logits)[-2:][::-1]
        best = float(logits[top2[0]])
        margin = best - float(logits[top2[1]])
        # one ulp of the model dtype at the top logit's magnitude
        ulp = float(jnp.finfo(cfg.param_dtype).eps) * 2.0 ** np.floor(
            np.log2(max(abs(best), 1e-30)))
        # both chosen tokens lie within the tolerance of the best logit;
        # with three or more tied logits the top-2 need not hold them both
        gap = max(best - float(logits[g[t]]), best - float(logits[w[t]]))
        near_tie = gap <= NEAR_TIE_ULPS * ulp
        print(f"[{name}] request {i}: first divergence at output token {t}"
              f" (got {g[t]}, reference {w[t]}); reference top-2 "
              f"{[int(x) for x in top2]} margin {margin:.6f} "
              f"({margin / ulp:.2f} ulps of {cfg.param_dtype}); chosen "
              f"tokens within {gap / ulp:.2f} ulps of the top -> "
              f"{'near-tie' if near_tie else 'NOT a near-tie'}")
        check(near_tie, f"{name}: request {i} diverges at token {t}")


def one_chip(args, cfg, model, params, clock) -> None:
    for kv in ("native", "int8"):
        got, _, _ = serve_once(f"kernels/{kv}", args, model, params, clock,
                               under_test=True, kv_dtype=kv)
        want, _, _ = serve_once(f"jnp-ref/{kv}", args, model, params, clock,
                                under_test=False, kv_dtype=kv,
                                use_ref_path=True)
        compare(f"kernels vs jnp-ref/{kv}", got, want, args, cfg, params,
                kv_dtype=kv)


def four_chips(args, cfg, model, params, clock) -> None:
    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, JAX sees {len(jax.devices())}")
    want, _, _ = serve_once("one-chip", args, model, params, clock,
                            under_test=True)
    fleet = argparse.Namespace(**{**vars(args), "replicas": 4})
    got, counters, placements = serve_once(
        "router x4", fleet, model, params, clock, under_test=True)
    check(sorted(p[0] for p in placements) == [0, 1, 2, 3]
          and all(len(p) == 1 for p in placements),
          f"router replicas are not one per device: {placements}")
    print(f"[router x4] placements per replica: "
          f"{[counters[f'placements_replica{i}'] for i in range(4)]}")
    compare("router x4 vs one-chip", got, want, args, cfg, params,
            kv_dtype="native")
    got, _, placements = serve_once("mesh kv=4", args, model, params, clock,
                                    under_test=True, serve_mesh="4")
    check(placements == [[0, 1, 2, 3]],
          f"serve mesh pools are not on the 4 devices: {placements}")
    compare("mesh kv=4 vs one-chip", got, want, args, cfg, params,
            kv_dtype="native")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args()

    dev = device_report()
    print(f"device: platform {dev['platform']} kind {dev['kind']} "
          f"count {dev['count']}")
    check(dev["platform"] == "tpu", "JAX found no TPU")
    check(not should_interpret(), "Pallas kernels would run interpreted")
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()

    args = serve.build_parser().parse_args(
        SERVE_ARGS + ["--seed", str(opts.seed)])
    c0, t0 = clock.seconds, time.perf_counter()
    cfg, model, params = serve.build_model_and_params(args)
    jax.block_until_ready(params)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    print(f"model: {cfg.name} layers {cfg.num_layers} d_model {cfg.d_model} "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads} head_dim {cfg.head_dim} "
          f"d_ff {cfg.d_ff} vocab {cfg.vocab_size}; {n_params} parameters; "
          f"init {time.perf_counter() - t0:.3f} s "
          f"(compile {clock.seconds - c0:.3f} s)")

    t0 = time.perf_counter()
    if opts.chips == 4:
        four_chips(args, cfg, model, params, clock)
    else:
        one_chip(args, cfg, model, params, clock)
    print(f"total: compile {clock.seconds:.3f} s, persistent-cache hits "
          f"{clock.cache_hits}, phases {time.perf_counter() - t0:.3f} s")
    for dev_id, peak in peak_bytes().items():
        print(f"device {dev_id}: peak_bytes_in_use {peak}")
    print(json.dumps({"ok": True, "device": device_report()}))


if __name__ == "__main__":
    main()
