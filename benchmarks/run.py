"""Benchmark harness: one section per paper table/figure + the roofline.

Invoke as ``python -m benchmarks.run`` from the repo root (the package
import form; plain ``python benchmarks/run.py`` also works via the
``__main__`` sys.path guard at the bottom of this file).

Prints a ``name,us_per_call,derived`` CSV block at the end (harness
contract).  Sections (select a subset with ``--only``):
  fig2     — matmul VM overhead vs DTLB size x problem size (bench_tlb_sweep)
  table1   — RiVEC suite scalar vs vector speedups           (bench_rivec)
  s31      — scheduler ticks + context switches              (bench_context_switch)
  serve    — seed vs Scheduler/Executor serving split        (bench_serve_throughput)
  sharded  — executor over the ('kv','hd') serve mesh        (bench_serve_sharded)
  router   — ReplicaRouter over N engines vs N=1             (bench_serve_router)
  prefix   — radix prefix cache: multi-turn chat, warm/cold  (bench_prefix_cache)
  quant    — int8 KV pools: accuracy envelope + bytes halved (bench_kv_quant)
  slo      — open-loop Poisson vs AOT-bucketed router        (bench_serve_slo)
  migrate  — swap migration + partial restore       (bench_restore_migration)
  c2       — burst vs element translation (+ coalescing)     (bench_translation)
  prefill  — gathered vs streamed continuation prefill       (bench_prefill_continue)
  pagesize — page-size sweep (TPU dual of the TLB sweep)     (bench_page_size)
  roof     — dry-run roofline table                          (roofline)

Seven sections double as CI gates when explicitly selected:
  * ``--only prefill`` exits nonzero if the chunked-prefill kernel path
    gathers at least as many bytes as the gathered-pages reference path;
  * ``--only serve`` exits nonzero unless auto-horizon greedy outputs are
    token-identical to the seed engine AND host syncs per decoded token
    are strictly below 1.0 AND the mean fused horizon exceeds 1.0 (batched
    K=1 decode already syncs less than once per token, so the sync ratio
    alone cannot detect the horizon silently regressing to K=1);
  * ``--only sharded`` exits nonzero unless the mesh-sharded executor is
    token-identical to the single-device KERNEL executor with the Pallas
    kernels LIVE (``kernel_dispatches > 0`` and ``ref_path_dispatches ==
    0`` — the jnp twin is reserved for the explicit ``--no-kernels``
    hatch), the scheduler counters are unchanged, AND the sharded kernel
    path gathers strictly fewer continuation-prefill KV bytes than the
    ref-path baseline engine.  Multi-device coverage needs
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
    ``multidevice`` job); with one device the mesh degrades to 1x1 and
    the gate still checks the kernel dispatch path;
  * ``--only router`` exits nonzero unless the replica sweep (a
    ReplicaRouter over N in {1,2,4} engines) is per-request
    token-identical to the N=1 reference AND the router's global
    page/counter accounting equals the sum of the per-replica
    accounting;
  * ``--only prefix`` exits nonzero unless the multi-turn chat workload
    skips more than half the cold engine's prefill tokens
    (``prefill_tokens_skipped / prefill_tokens_cold > 0.5``) while every
    (session, turn) stream stays token-identical to the cold-admission
    reference;
  * ``--only quant`` exits nonzero unless int8 KV pools keep the kernels
    live (``ref_path_dispatches == 0``, ``quant_dispatches > 0`` on both
    the single-device and mesh engines), stay token-identical to the jnp
    ref oracle and the mesh engine, hold greedy top-1 agreement vs the
    fp-pool engine at or above the fixed threshold, shrink bytes-per-page
    and bytes_spilled by exactly the pool itemsize ratio (>= 2x) over the
    SAME spilled pages, and still gather strictly fewer continuation-
    prefill bytes than the int8 ref baseline;
  * ``--only slo`` exits nonzero unless the open-loop Poisson runs (each
    QPS level on a fresh AOT-bucketed engine behind an N=1 router) stay
    per-request token-identical to a closed-loop unbucketed reference,
    the streamed events match the drained results, and after warmup
    ``aot_misses == 0`` with ``aot_hits > 0``.  TTFT/TPOT p50/p99 and
    queue depth are recorded, never wall-clock-gated;
  * ``--only migrate`` exits nonzero unless the skewed heterogeneous
    two-replica fleet with migration ON completes EVERY request
    token-identically to the roomy single-replica reference with
    ``failed_unreachable == 0``, ``reach_redirects > 0`` and
    ``restore_migrations > 0`` (real KV pages exported from the starved
    small pool and adopted by the roomy one), while the reach-blind
    ``migrate=False`` baseline on the SAME load shows
    ``failed_unreachable > 0`` (the stranding being fixed — a baseline
    that stops failing means the scenario went vacuous, which is also a
    gate failure); the tight-pool partial-restore phase must show
    ``partial_restores > 0`` / ``pages_refilled > 0`` token-identically,
    and no engine may leak a swap record
    (``ContextSwitcher.swapped_out`` empty at every drain).

The serve, sharded, router, prefix, quant, slo and migrate sections also
append their metrics (tagged
with a ``section`` field) to ``BENCH_serve.json`` at the repo root — the
machine-readable perf trajectory across PRs, which
``scripts/bench_regress.py`` gates on per section (counters only, never
tok/s).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time


def section(title: str):
    print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}")


def _fig2():
    from benchmarks import bench_tlb_sweep
    return bench_tlb_sweep.main()


def _table1():
    from benchmarks import bench_rivec
    return bench_rivec.main()


def _s31():
    from benchmarks import bench_context_switch
    return bench_context_switch.main()


def _record_serve_trajectory(metrics: dict, section: str = "serve") -> None:
    """Append the metrics to ``BENCH_serve.json`` (repo root): a JSON
    array, one record per benchmark run, so the perf trajectory across PRs
    is machine-readable instead of buried in CI logs.  Records are tagged
    with their ``section`` (``serve``, ``router``, ...) so
    ``scripts/bench_regress.py`` compares like with like; untagged legacy
    records read as ``serve``."""
    path = pathlib.Path(__file__).resolve().parent.parent / "BENCH_serve.json"
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text())
        except (OSError, ValueError):
            history = None
        if not isinstance(history, list):
            # never silently overwrite an existing trajectory: move the
            # unreadable/malformed file aside and start a fresh history
            backup = path.with_name(path.name + ".corrupt")
            path.replace(backup)
            print(f"WARNING: {path.name} was unreadable; moved to "
                  f"{backup.name}, starting a fresh trajectory")
            history = []
    history.append(
        {"t": time.strftime("%Y-%m-%dT%H:%M:%S"), "section": section,
         "metrics": metrics}
    )
    path.write_text(json.dumps(history, indent=2) + "\n")
    print(f"trajectory -> {path} ({len(history)} records)")


def _serve(gate: bool = False):
    from benchmarks import bench_serve_throughput
    csv, metrics = bench_serve_throughput.run()
    _record_serve_trajectory(metrics)
    failures = []
    if not metrics["token_identical"]:
        failures.append("auto-horizon greedy outputs diverged from the "
                        "seed engine")
    if metrics["host_syncs_per_token"] >= 1.0:
        failures.append(
            f"host syncs per decoded token = "
            f"{metrics['host_syncs_per_token']:.3f} (must be < 1.0: the "
            "fused horizon must amortize the per-token host round-trip)")
    if metrics["mean_horizon"] <= 1.0:
        failures.append(
            f"mean fused horizon = {metrics['mean_horizon']:.2f} (must be "
            "> 1.0: the auto horizon never opened on the quiet sweep "
            "workload — fusion is silently disabled)")
    for f in failures:
        print(f"FAIL: {f}")
    if failures and gate:          # --only serve: act as a CI gate
        sys.exit(1)
    return csv


def _sharded(gate: bool = False):
    from benchmarks import bench_serve_sharded
    csv, metrics = bench_serve_sharded.run()
    _record_serve_trajectory(metrics, section="sharded")
    failures = []
    if not metrics["token_identical"]:
        failures.append(
            f"sharded executor ({metrics['mesh_devices']} mesh devices) "
            "diverged from the single-device kernel token stream")
    if not metrics["counters_identical"]:
        failures.append(
            "scheduler counters changed under sharding — the data-plane "
            "layout leaked into policy decisions")
    if not metrics["kernels_live"]:
        failures.append(
            f"kernels not live on the mesh: kernel_dispatches="
            f"{metrics['kernel_dispatches']}, ref_path_dispatches="
            f"{metrics['ref_path_dispatches']} (every compute step must "
            "dispatch the Pallas kernels through shard_map; the jnp twin "
            "is reserved for the explicit --no-kernels hatch)")
    if not metrics["bytes_win"]:
        failures.append(
            f"continuation prefill gathered "
            f"{metrics['prefill_bytes_gathered_kernel']} B on the kernel "
            f"path vs {metrics['prefill_bytes_gathered_ref']} B on the ref "
            "path — the sharded kernel must gather strictly fewer KV bytes")
    for f in failures:
        print(f"FAIL: {f}")
    if failures and gate:          # --only sharded: act as a CI gate
        sys.exit(1)
    return csv


def _router(gate: bool = False):
    from benchmarks import bench_serve_router
    csv, metrics = bench_serve_router.run()
    _record_serve_trajectory(metrics, section="router")
    failures = []
    if not metrics["token_identical"]:
        failures.append(
            "replica-sweep outputs diverged from the N=1 reference run "
            "(or done statuses are not a permutation of it)")
    if not metrics["accounting_identical"]:
        failures.append(
            "router global page/counter accounting != sum of per-replica "
            "accounting")
    for f in failures:
        print(f"FAIL: {f}")
    if failures and gate:          # --only router: act as a CI gate
        sys.exit(1)
    return csv


def _prefix(gate: bool = False):
    from benchmarks import bench_prefix_cache
    csv, metrics = bench_prefix_cache.run()
    _record_serve_trajectory(metrics, section="prefix")
    failures = []
    if not metrics["token_identical"]:
        failures.append(
            "radix-hit streams diverged from the cold-admission reference "
            "(a COW-mapped prefix must reproduce full-prefill state "
            "exactly)")
    if metrics["skip_ratio"] <= 0.5:
        failures.append(
            f"skip ratio = {metrics['skip_ratio']:.2f} (must be > 0.5: "
            "the multi-turn chat workload re-prefills history the radix "
            "cache should be serving from resident pages)")
    for f in failures:
        print(f"FAIL: {f}")
    if failures and gate:          # --only prefix: act as a CI gate
        sys.exit(1)
    return csv


def _quant(gate: bool = False):
    from benchmarks import bench_kv_quant
    csv, metrics = bench_kv_quant.run()
    _record_serve_trajectory(metrics, section="quant")
    failures = []
    if not metrics["kernels_live"]:
        failures.append(
            f"kernels not live under int8 pools: "
            f"kernel={metrics['kernel_dispatches_int8']}/"
            f"ref={metrics['ref_path_dispatches_int8']}/"
            f"quant={metrics['quant_dispatches_int8']} single-device, "
            f"kernel={metrics['kernel_dispatches_int8_mesh']}/"
            f"ref={metrics['ref_path_dispatches_int8_mesh']}/"
            f"quant={metrics['quant_dispatches_int8_mesh']} mesh "
            "(quantization must ride the kernel dispatch, not the ref "
            "hatch)")
    if not metrics["token_identical_ref"]:
        failures.append(
            "int8 kernel tokens diverged from the int8 jnp ref oracle — "
            "the in-kernel dequant disagrees with the differential "
            "baseline")
    if not metrics["token_identical_mesh"]:
        failures.append(
            f"int8 mesh engine ({metrics['mesh_devices']} devices) "
            "diverged from the single-device int8 kernel stream")
    if metrics["top1_agreement"] < metrics["agreement_threshold"]:
        failures.append(
            f"greedy top-1 agreement vs the fp engine = "
            f"{metrics['top1_agreement']:.3f} (threshold "
            f"{metrics['agreement_threshold']}: the accuracy envelope "
            "collapsed)")
    if not metrics["bytes_halved"]:
        failures.append(
            f"bytes-per-page {metrics['bytes_per_page_fp']} -> "
            f"{metrics['bytes_per_page_int8']} is not the exact itemsize "
            "ratio (>= 2x) — quantized pools are not actually narrow")
    if not metrics["spill_halved"]:
        failures.append(
            f"bytes_spilled {metrics['bytes_spilled_fp']} -> "
            f"{metrics['bytes_spilled_int8']} over "
            f"{metrics['pages_spilled_fp']} vs "
            f"{metrics['pages_spilled_int8']} pages — spills must move "
            "the SAME pages at the itemsize-ratio fewer bytes (and the "
            "workload must actually spill)")
    if not metrics["bytes_win"]:
        failures.append(
            f"continuation prefill gathered "
            f"{metrics['prefill_bytes_gathered_int8']} B on the int8 "
            f"kernel path vs {metrics['prefill_bytes_gathered_int8_ref']} "
            "B on the int8 ref path — quantization must not forfeit the "
            "page-streaming win")
    for f in failures:
        print(f"FAIL: {f}")
    if failures and gate:          # --only quant: act as a CI gate
        sys.exit(1)
    return csv


def _slo(gate: bool = False):
    from benchmarks import bench_serve_slo
    csv, metrics = bench_serve_slo.run()
    _record_serve_trajectory(metrics, section="slo")
    failures = []
    if not metrics["token_identical"]:
        failures.append(
            "open-loop token streams diverged from the closed-loop "
            "unbucketed reference (AOT padding or arrival-time scheduling "
            "leaked into the tokens)")
    if not metrics["streams_identical"]:
        failures.append(
            "streamed events disagree with the drained results — the "
            "async detokenize pipeline dropped/reordered tokens")
    if metrics["aot_misses"] != 0:
        failures.append(
            f"aot_misses = {metrics['aot_misses']} after warmup (must be "
            "0: every serving prefill must hit a build-time-compiled "
            "executable — a miss is a potential jit stall under load)")
    if metrics["aot_hits"] <= 0:
        failures.append(
            "aot_hits == 0: the bucketed path never dispatched — the "
            "gate is vacuous")
    for f in failures:
        print(f"FAIL: {f}")
    if failures and gate:          # --only slo: act as a CI gate
        sys.exit(1)
    return csv


def _migrate(gate: bool = False):
    from benchmarks import bench_restore_migration
    csv, metrics = bench_restore_migration.run()
    _record_serve_trajectory(metrics, section="migrate")
    failures = []
    if not metrics["token_identical"]:
        failures.append(
            "migrating-fleet outputs diverged from the roomy single-replica "
            "reference (or a request did not finish) — migration must be a "
            "timing policy, never a token policy")
    if not metrics["partial_token_identical"]:
        failures.append(
            "partial-restore outputs diverged from the roomy reference (or "
            "a request did not finish) — the re-prefilled tail must "
            "reproduce the evicted KV exactly")
    if not metrics["accounting_identical"]:
        failures.append(
            "router global page/counter accounting != sum of per-replica "
            "accounting after migration")
    if metrics["failed_unreachable_migrate"] != 0:
        failures.append(
            f"failed_unreachable = {metrics['failed_unreachable_migrate']} "
            "with migration ON (must be 0: no request may fail while any "
            "replica can host it)")
    if metrics["failed_unreachable_baseline"] <= 0:
        failures.append(
            "the migrate=False baseline stranded nothing — the skewed "
            "workload no longer exercises the failure the gate exists to "
            "prevent (vacuous scenario)")
    if metrics["restore_migrations"] <= 0:
        failures.append(
            "restore_migrations == 0: no starved victim ever moved through "
            "the portable-swap path — the migration machinery went inert")
    if metrics["reach_redirects"] <= 0:
        failures.append(
            "reach_redirects == 0: placement never overrode a reach-blind "
            "choice on the heterogeneous fleet")
    if metrics["partial_restores"] <= 0 or metrics["pages_refilled"] <= 0:
        failures.append(
            f"partial_restores = {metrics['partial_restores']}, "
            f"pages_refilled = {metrics['pages_refilled']} (both must be "
            "> 0: the capacity-blocked head never came back early)")
    if metrics["swap_record_leaks"] != 0:
        failures.append(
            f"{metrics['swap_record_leaks']} swap records left on a "
            "ContextSwitcher at drain — a terminal path forgot to "
            "restore/export/discard its spill")
    for f in failures:
        print(f"FAIL: {f}")
    if failures and gate:          # --only migrate: act as a CI gate
        sys.exit(1)
    return csv


def _c2():
    from benchmarks import bench_translation
    return bench_translation.main()


def _prefill(gate: bool = False):
    from benchmarks import bench_prefill_continue
    csv, metrics = bench_prefill_continue.run()
    if metrics["kernel_bytes"] >= metrics["ref_bytes"]:
        print(f"FAIL: kernel path gathered {metrics['kernel_bytes']} B, "
              f"reference gathered {metrics['ref_bytes']} B — the streamed "
              "path must touch strictly fewer bytes")
        if gate:              # --only prefill: act as a CI gate
            sys.exit(1)
    return csv


def _pagesize():
    from benchmarks import bench_page_size
    return bench_page_size.main()


def _roof():
    from benchmarks import roofline
    return roofline.main()


SECTIONS: list[tuple[str, str, object]] = [
    ("fig2", "Fig. 2(b,c,d): matmul VM overhead vs DTLB size", _fig2),
    ("table1", "Table 1: RiVEC suite (S / V / Vu)", _table1),
    ("s31", "§3.1: scheduler interrupts + context switches", _s31),
    ("serve", "Serving split: seed vs Scheduler/Executor (decode + switches)",
     _serve),
    ("sharded",
     "Sharded executor over the ('kv','hd') serve mesh vs single-device",
     _sharded),
    ("router",
     "Replica sweep: ReplicaRouter over N engines vs the N=1 reference",
     _router),
    ("prefix",
     "Radix prefix cache: multi-turn chat, warm (radix) vs cold admission",
     _prefix),
    ("quant",
     "Quantized int8 KV pools: accuracy envelope + bytes-per-page halving",
     _quant),
    ("slo",
     "Open-loop SLO: Poisson arrivals vs AOT-bucketed router (TTFT/TPOT)",
     _slo),
    ("migrate",
     "Swap migration: skewed heterogeneous fleet + partial restore",
     _migrate),
    ("c2", "C2: translation counts (burst / element / coalesced)", _c2),
    ("prefill",
     "Chunked prefill: gathered-pages oracle vs page-streaming kernel",
     _prefill),
    ("pagesize",
     "Beyond-paper: page-size sweep (the TPU dual of the TLB sweep)",
     _pagesize),
    ("roof", "Roofline (from dry-run artifacts)", _roof),
]


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", choices=[k for k, _, _ in SECTIONS],
                    action="append", default=None,
                    help="run only the named section(s); repeatable")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    t0 = time.time()
    csv: list[str] = ["name,us_per_call,derived"]
    for key, title, fn in SECTIONS:
        if args.only is not None and key not in args.only:
            continue
        section(title)
        if key in ("prefill", "serve", "sharded", "router", "prefix",
                   "quant", "slo", "migrate"):
            # the gates abort only when explicitly selected; a full run
            # must still emit the complete CSV block
            csv += fn(gate=args.only is not None)
        else:
            csv += fn()
    section(f"CSV (total {time.time() - t0:.0f}s)")
    for line in csv:
        print(line)


if __name__ == "__main__":
    if __package__ in (None, ""):
        # `python benchmarks/run.py`: the script's own directory is on
        # sys.path but the repo root is not, so the `from benchmarks
        # import ...` inside each section would fail with a confusing
        # ModuleNotFoundError.  Put the repo root (and src/, for `repro`
        # itself when PYTHONPATH is unset) first so both invocation forms
        # work (`python -m benchmarks.run` is the canonical one).
        _root = pathlib.Path(__file__).resolve().parent.parent
        sys.path.insert(0, str(_root / "src"))
        sys.path.insert(0, str(_root))
    main()
