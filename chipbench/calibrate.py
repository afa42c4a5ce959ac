#!/usr/bin/env python3
"""Readings that the correctness limit of a configuration is set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds N [N ...] \\
        --seconds S --trace 0|1 [--kv-int8-seeds K]

For each seed, in one process: one run of the cell, exactly as ``run.py``
makes it (its result line is printed first, so the run also counts as
one of a set, though only the first pays a fresh process's set-up), and
then, at the same prompts and served tokens, ``run.judge`` with each
reference control (int8, fp8 matmuls) put in the program's place: the
widest gap of the token that the control puts first below the
reference's best, and the ``correct`` that it gets against the
configuration's limit.  For the first ``K`` seeds one more window
follows with the program's own int8 KV pools switched on, judged like
the program.  After each seed one JSON line of these readings.  The
benchmark's own runs never run a control.
"""

from __future__ import annotations

import argparse
import json
import sys

import run

#: the precisions below bf16 read as controls
CONTROLS = ("int8", "fp8")


def readings(cell, seed: int, seconds: float, trace: bool, kv_int8: bool,
             clock) -> dict:
    sv = run.serve(cell, seed, seconds, trace, clock=clock)
    cache: dict = {}
    result = run.result_of(cell, seed, sv, cache=cache)
    print(json.dumps(result), flush=True)
    line = {"seed": seed,
            "program_max_gap": result["checks"]["max_logit_gap"]["value"],
            "tokens": result["checks"]["compared_tokens"]["value"],
            "program_correct": result["correct"]}
    for c in CONTROLS:
        ok, checks = run.judge(cell, seed, sv.rows, control=c, cache=cache)
        line.update({f"{c}_max_gap": checks["max_logit_gap"]["value"],
                     f"{c}_correct": ok})
    cache.clear()
    if kv_int8:
        sv = run.serve(cell, seed, seconds, False, clock=clock,
                       kv_dtype="int8")
        ok, checks = run.judge(cell, seed, sv.rows)
        line.update(kv_int8_max_gap=checks["max_logit_gap"]["value"],
                    kv_int8_correct=ok)
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--kv-int8-seeds", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.HERE.parent / "src"))
    cell = run.find_cell(run.HERE.parent, args.workload)
    run.check_chip(cell.chips)
    run.enable_compile_cache()
    clock = run.CompileClock()
    for i, seed in enumerate(args.seeds):
        line = readings(cell, seed, args.seconds, bool(args.trace),
                        i < args.kv_int8_seeds, clock)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
