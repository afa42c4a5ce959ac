#!/usr/bin/env python3
"""Chip benchmark of the paged-KV serving engine, one cell per process.

    python3 chipbench/run.py --workload <config>.<mix> --seed N \\
        --seconds S --trace 0|1

A cell of ``BENCHMARK.json`` names a configuration,
``chipbench/configs/<config>.json``, and a traffic mix,
``chipbench/traffic/<mix>.json``.  A run draws the weights on the chip
from the seed, builds the engine through the program's own entry points
(``Engine``, ``ServeConfig``, ``ServeRequest``) with the mix's AOT prefill
buckets, warms up every shape the window can use, drives the mix's
traffic for ``--seconds`` on the wall clock, and then checks what was
served against the plain reference (``reference.py``) once the engine is
freed.  Each metric of the cell is read by ``metrics/<name>.py``: with
``--trace 0`` the end-to-end metrics, with ``--trace 1`` the per-layer
ones, over a profiler trace of a slice of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and last ``checks``, each number compared with its
limit.  The run exits nonzero, with no result line, when JAX finds no
TPU or fewer chips than the cell asks for, when Pallas would run
interpreted, or when a compute step ran on the program's jnp reference
path.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import latency  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402


class NoChip(RuntimeError):
    """The machine cannot run this cell as measured (no TPU, too few
    chips, interpreted kernels)."""


class RefPath(RuntimeError):
    """A compute step ran on the program's jnp reference path."""


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def find_cell(root: pathlib.Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / confs[w["config"]]["file"])
    mix = load_json(root / HERE.name / "traffic" / f"{w['traffic']}.json")

    def mine(m: dict) -> bool:
        return name in m.get("workloads", [name])

    return Cell(name, int(w["chips"]), config, mix,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


def _module(kind: str, name: str):
    """The file ``<kind>/<name>.py`` of the benchmark, as a module."""
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """``metrics/<name>.py``, whose ``read(record)`` gives the metric."""
    return _module("metrics", name)


def arrival_law(name: str):
    """``arrivals/<name>.py``, whose ``drive(feeder, mix, vocab, seconds,
    seed, tracer)`` runs the window and returns ``(t0, t1, t_end)``."""
    return _module("arrivals", name)


# ---------------------------------------------------------------------------
# clocks, devices
# ---------------------------------------------------------------------------


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from
    the persistent cache), from JAX's own monitoring events.  Nested jits
    report spans inside their caller's, so the spans are merged before
    they are summed.  ``count(t0, t1)`` is the number of such events that
    ended inside ``[t0, t1)``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self._spans: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_time)

    def _on_time(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            end = time.perf_counter()       # reported as the span closes
            self._spans.append((end - secs, end))

    @property
    def seconds(self) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self._spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total

    def count(self, t0: float, t1: float) -> int:
        return sum(1 for _, end in self._spans if t0 <= end < t1)


def device_report() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def check_chip(chips: int) -> None:
    dev = device_report()
    if dev["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev['platform']})")
    if dev["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {dev['count']}")
    if jax.default_backend() != "tpu":
        # the program interprets its Pallas kernels off the TPU backend
        raise NoChip("Pallas kernels would run interpreted")


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else a fixed directory of the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(HERE.parent / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def family(cfg: dict):
    """``families/<family>.py`` of the configuration: its weights, its
    reference, and the program's model for it."""
    return _module("families", cfg["family"])


def build_engine(cfg: dict, mix: dict, params: dict,
                 kv_dtype: str = "native"):
    from repro.serve import Engine, ServeConfig
    model = family(cfg).model(cfg)
    page = mix["page_size"]
    serve_cfg = ServeConfig(
        page_size=page, num_pages=mix["pool_tokens"] // page + 1,
        max_pages_per_seq=mix["reach_tokens"] // page,
        max_batch=mix["max_batch"], greedy=True,
        aot_buckets=tuple(mix["aot_buckets"]), kv_dtype=kv_dtype)
    return Engine(model, params, serve_cfg)


def instrument(engine, log: list[dict]) -> None:
    """Record every data-plane call (kind, host start/end, real lengths)
    and mark it on the profiler's host timeline.  Wraps the executor
    object's own methods; the program is not changed."""
    ex = engine.executor

    def wrap(kind, fn, info):
        def call(*args):
            rec = {"kind": kind, **info(*args)}
            with jax.profiler.TraceAnnotation(f"exec.{kind}"):
                rec["t0"] = time.perf_counter()
                out = fn(*args)
                rec["t1"] = time.perf_counter()
            log.append(rec)
            return out
        return call

    def prefill_info(reqs):
        return {"lens": [len(r.prompt) for r in reqs]}

    def continue_info(reqs, starts, _tails):
        return {"lens": [len(r.prompt) for r in reqs],
                "starts": [int(s) for s in starts]}

    def decode_info(tokens, pre_lens, active):
        return {"ctxs": [int(p) + 1 for p, a in zip(pre_lens, active) if a],
                "horizon": 1}

    def multi_info(plan):
        ctxs = [int(p) + t + 1
                for p, s in zip(plan.pre_lens, plan.steps_left)
                for t in range(int(s))]
        return {"ctxs": ctxs, "horizon": plan.horizon}

    ex.prefill = wrap("prefill", ex.prefill, prefill_info)
    ex.admit_forked_batch = wrap("continue", ex.admit_forked_batch,
                                 continue_info)
    ex.decode = wrap("decode", ex.decode, decode_info)
    ex.decode_multi = wrap("decode", ex.decode_multi, multi_info)


def warm_shapes(engine, vocab: int, rng: np.random.Generator) -> None:
    """Run every program the window can dispatch once, at every shape,
    through the engine's public calls only.

    An admission of ``n`` rows updates ``n`` page-table rows and gathers
    them, one shape per ``n``: so ``n`` one-page requests of one token
    each are admitted together, for ``n`` from 1 to ``max_batch``.  One
    request of ``2 * max_horizon`` tokens then runs every fused decode
    horizon (after its first token the scheduler picks the largest power
    of two of the steps left: 8, 4, 2, 1), and one request per AOT prefill
    bucket fills that bucket."""
    from repro.serve import ServeRequest
    cfg = engine.cfg

    def serve(lengths: list[int], new: int) -> None:
        for n in lengths:
            prompt = rng.integers(0, vocab, size=n).astype(np.int32)
            engine.submit(ServeRequest(prompt=prompt, max_new_tokens=new))
        while engine.scheduler.has_work:
            engine.step()

    for n in range(1, cfg.max_batch + 1):
        serve([cfg.page_size] * n, 1)
    serve([cfg.page_size], 2 * cfg.max_horizon)
    for bucket in cfg.aot_buckets:
        serve([bucket], 2)


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------


class Feeder:
    """Submits traffic, steps the engine and keeps each request's commit
    stamps (the scheduler's own ``t_first_token``/``t_last_token``)."""

    def __init__(self, engine):
        self.engine = engine
        self.logs: list[latency.RequestLog] = []
        self.reqs: list = []              # the scheduler's Request objects
        self._live: dict[int, tuple] = {}

    def submit(self, item: traffic.Item, due: float | None) -> None:
        from repro.serve import ServeRequest
        with jax.profiler.TraceAnnotation("chipbench.submit"):
            rid = self.engine.submit(ServeRequest(
                prompt=item.prompt, max_new_tokens=item.max_new))
            req = self.engine.scheduler.queue[-1]
            assert req.req_id == rid
            log = latency.RequestLog(len(item.prompt), item.max_new, due,
                                     time.perf_counter())
        self.logs.append(log)
        self.reqs.append(req)
        self._live[rid] = (req, log)

    def step(self) -> None:
        with jax.profiler.TraceAnnotation("chipbench.step"):
            self.engine.step()
        with jax.profiler.TraceAnnotation("chipbench.observe"):
            for rid, (req, log) in list(self._live.items()):
                n, seen = len(req.output), log.served
                if n > seen:
                    if seen == 0:
                        log.commits.append((req.t_first_token, 1))
                        seen = 1
                    if n > seen:
                        log.commits.append((req.t_last_token, n - seen))
                if req.status in ("done", "failed"):
                    del self._live[rid]

    @property
    def queued(self) -> int:
        return len(self.engine.scheduler.queue)


class Tracer:
    """When enabled, profiles a slice of the window: it starts
    ``mix["trace"]["start_s"]`` after the window opens and lasts
    ``length_s`` from the moment the profiler is running (each clamped to
    a third of a short window)."""

    def __init__(self, enabled: bool, mix: dict, seconds: float):
        self.enabled = enabled
        self.start = min(float(mix["trace"]["start_s"]), seconds / 3)
        self.length = min(float(mix["trace"]["length_s"]), seconds / 3)
        self.dir = None
        self._mark = None
        self.host: tuple[float, float] | None = None

    def poll(self, at: float) -> None:
        """``at``: seconds since the window opened (``inf`` once closed)."""
        if not self.enabled:
            return
        now = time.perf_counter()
        if self.dir is None and self.start <= at < float("inf"):
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._mark = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
            self._mark.__enter__()
            self.host = (time.perf_counter(), float("inf"))
        elif self._mark is not None and (
                now >= self.host[0] + self.length or at == float("inf")):
            self._mark.__exit__(None, None, None)
            self._mark = None
            self.host = (self.host[0], time.perf_counter())
            jax.profiler.stop_trace()

    def reduction(self) -> trace_reduce.Reduction | None:
        if not self.enabled or self.dir is None:
            return None
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        try:
            if not files:
                raise RuntimeError(f"no trace written under {self.dir}")
            return trace_reduce.reduce(trace_reduce.load(files[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def check_sample(feeder: Feeder, rows: int, seed: int) -> list[int]:
    """Indices of the requests compared: the one with the most served
    tokens and, drawn from the seed, others among those that served any."""
    served = [i for i, log in enumerate(feeder.logs) if log.served > 0]
    if not served:
        return []
    longest = max(served, key=lambda i: feeder.logs[i].served)
    rest = [i for i in served if i != longest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), size=min(rows - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def served_rows(feeder: Feeder, picks: list[int]):
    return [(np.asarray(feeder.reqs[i].prompt, np.int32),
             np.asarray([int(t) for t in feeder.reqs[i].output], np.int32))
            for i in picks]


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Record:
    """Everything a metric reader may read."""

    cell: Cell
    peak: dict
    setup_s: float
    t0: float
    t1: float
    t_end: float
    logs: list[latency.RequestLog]
    counters: dict[str, float]       # deltas over the window
    dispatches: list[dict]           # data-plane calls in the window
    trace: trace_reduce.Reduction | None = None
    traced: list[dict] = dataclasses.field(default_factory=list)
    trace_on: bool = False           # a --trace 1 run

    @property
    def cfg(self) -> dict:
        return self.cell.config

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


@dataclasses.dataclass
class Served:
    """A measured window, with the engine already freed."""

    rec: Record
    rows: list[tuple[np.ndarray, np.ndarray]]   # (prompt, served) compared
    statuses: list[str]
    device: dict
    info: dict


def serve(cell: Cell, seed: int, seconds: float, trace: bool, *,
          clock: CompileClock, require_chip: bool = True, hook=None,
          t_process: float = T_PROCESS, kv_dtype: str = "native") -> Served:
    """Build, warm up, drive the window, read the counters and the peak
    memory, pick the rows to compare and free the engine.  ``hook``, when
    given, is called with the engine before warm-up (tests break the timed
    path through it); ``kv_dtype="int8"`` switches on the program's own
    int8 KV pools, the control ``calibrate.py`` reads."""
    cfg, mix = cell.config, cell.mix
    if require_chip:
        check_chip(cell.chips)
    dev = device_report()
    peak = peaks_for(dev["kind"]) if require_chip else {}
    print(f"device: {dev}", file=sys.stderr)

    fam = family(cfg)
    w = fam.make_weights(cfg, seed)
    engine = build_engine(cfg, mix, fam.program_params(w), kv_dtype)
    dispatches: list[dict] = []
    instrument(engine, dispatches)
    if hook is not None:
        hook(engine)
    warm_shapes(engine, cfg["vocab_size"], np.random.default_rng([seed, 2]))
    feeder = Feeder(engine)
    tracer = Tracer(trace, mix, seconds)
    law = arrival_law(mix["arrivals"])
    c_before = dict(engine.counters.counters)
    t_warm = time.perf_counter()
    t0, t1, t_end = law.drive(feeder, mix, cfg["vocab_size"], seconds, seed,
                              tracer)
    # the lead-in or warm-up traffic before the window is set-up too
    setup_s = t0 - t_process
    c_after = dict(engine.counters.counters)
    counters = {k: c_after.get(k, 0) - c_before.get(k, 0) for k in c_after}
    mem_peak = memory_peak_bytes() if require_chip else 0
    if counters.get("ref_path_dispatches", 0) > 0 or (
            counters.get("kernel_dispatches", 0) == 0):
        raise RefPath(f"kernel_dispatches {counters.get('kernel_dispatches')}"
                      f" ref_path_dispatches "
                      f"{counters.get('ref_path_dispatches')}")
    red = tracer.reduction()
    in_window = [d for d in dispatches if t0 <= d["t0"] and d["t1"] <= t1]
    traced = []
    if tracer.host is not None:
        a, b = tracer.host
        traced = [d for d in dispatches if a <= d["t0"] and d["t1"] <= b]
    rec = Record(cell, peak, setup_s, t0, t1, t_end, feeder.logs, counters,
                 in_window, red, traced, trace)
    rows = served_rows(feeder, check_sample(
        feeder, int(mix["check"]["rows"]), seed))
    statuses = [r.status for r in feeder.reqs]
    engine.close()
    del engine, feeder.engine, w
    gc.collect()
    device = {**dev, "memory_peak_bytes": mem_peak}
    if red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
    late = [log.submitted - log.due for log in feeder.logs
            if log.due is not None and t0 <= log.due < t1]
    ttft, _ = latency.ttfts(feeder.logs, t0, t1, t_end)
    info = {
        "setup_s": setup_s, "build_and_warm_s": t_warm - t_process,
        "compile_s": clock.seconds, "window_compiles": clock.count(t0, t1),
        "window_s": t1 - t0, "drain_s": t_end - t1,
        "ttft_n": len(ttft), "ttft_p50_ms":
            latency.percentile(ttft, 50) * 1e3 if ttft else None,
        "tpot_n": len(latency.tpots(feeder.logs, t0, t1)),
        "generator_late_p99_ms":
            latency.percentile(late, 99) * 1e3 if late else None,
        "dispatches": len(in_window), "counters": {
            k: counters.get(k, 0) for k in (
                "decode_dispatches", "decode_horizon", "prefill_tokens",
                "bucket_pad_tokens", "aot_hits", "aot_misses",
                "kernel_dispatches", "ref_path_dispatches", "preemptions")},
        "compared_rows": len(rows),
    }
    return Served(rec, rows, statuses, device, info)


def judge(cell: Cell, seed: int, rows: list[tuple[np.ndarray, np.ndarray]],
          control: str | None = None,
          cache: dict | None = None) -> tuple[bool, dict]:
    """``(correct, checks)`` for the served ``rows``: the widest gap by
    which a served token's logit lies below the reference's best, against
    the configuration's limit, over at least the mix's number of tokens.

    ``control`` (``"int8"`` or ``"fp8"``) puts the reference at that
    precision in the program's place: the tokens judged are then, at the
    same positions, the ones the control puts first.  ``cache`` (a dict)
    keeps the float32 reference's hidden states between calls on the same
    rows."""
    cfg, check = cell.config, cell.mix["check"]
    gap = float("inf")
    if rows:
        fam = family(cfg)
        w = fam.make_weights(cfg, seed)
        served, ctl = fam.logit_gaps(cfg, w, rows, int(check["length"]),
                                     control=control, cache=cache)
        del w
        gap = float((ctl if control else served).max())
    limit = cfg["correct"]["max_logit_gap"]
    tokens = int(sum(len(s) for _, s in rows))
    checks = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "compared_tokens": {"value": tokens,
                            "limit": int(check["min_tokens"])},
    }
    correct = (limit is not None and gap <= float(limit)
               and tokens >= int(check["min_tokens"]))
    return correct, checks


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: pathlib.Path = HERE.parent, require_chip: bool = True,
        hook=None, t_process: float = T_PROCESS,
        control: str | None = None) -> dict:
    """One run of cell ``workload``; returns the result object.
    ``control`` judges a reference control in the program's place
    (:func:`judge`); the benchmark's own runs never set it."""
    cell = find_cell(root, workload)
    sv = serve(cell, seed, seconds, trace, clock=CompileClock(),
               require_chip=require_chip, hook=hook, t_process=t_process)
    return result_of(cell, seed, sv, control)


def result_of(cell: Cell, seed: int, sv: Served,
              control: str | None = None, cache: dict | None = None) -> dict:
    """The result object of a served window: ``correct`` from the sampled
    rows against the reference (the engine's state already freed), the
    cell's metrics, and last the checks."""
    rec = sv.rec
    t_ref = time.perf_counter()
    correct, checks = judge(cell, seed, sv.rows, control, cache)
    sv.info["reference_s"] = time.perf_counter() - t_ref

    metrics = {}
    for m in (cell.per_layer if rec.trace_on else cell.end_to_end):
        value = reader(m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # requests sent before the close and not already finished at the open
    attempted = sum(1 for log in rec.logs if log.submitted < rec.t1 and not (
        log.served >= log.max_new and log.commits[-1][0] < rec.t0))
    _, missing = latency.ttfts(rec.logs, rec.t0, rec.t1, rec.t_end)
    failed = missing + sum(1 for s in sv.statuses if s == "failed")
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": sv.device}
    if rec.trace is not None:
        result["breakdown"] = {
            "device_ops": trace_reduce.top(rec.trace.by_op),
            "idle_gaps": trace_reduce.top(rec.trace.idle_by_host)}
    result["checks"] = checks
    print("info " + json.dumps(sv.info), file=sys.stderr)
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE.parent / "src"))
    try:
        check_chip(find_cell(HERE.parent, args.workload).chips)
        print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        print(f"chipbench: no measurement: {e}", file=sys.stderr)
        return 3
    except RefPath as e:
        print(f"chipbench: compute ran off the kernels: {e}", file=sys.stderr)
        return 4
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
