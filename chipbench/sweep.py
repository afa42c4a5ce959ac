#!/usr/bin/env python3
"""Sweep the arrival rate of an open-loop cell to find its knee.

    python3 chipbench/sweep.py --workload qwen2-7b.chat --seed N \\
        --seconds S --rates 0.5 1 1.5 2

For each rate, in one process: one window of the cell at that rate (the
mix's file is not changed).  Prints per rate the requests due, the
backlog (due without a first token) a quarter into the window and at its
close, TTFT p50/p90 and output tokens/s.  The rates run in ascending
order and stop after the first whose backlog grows over the window (by
more than one request from the quarter to the close, or with a request
due in the window left without its first token).  The knee is the
highest rate before it; the last line gives it and 0.8 times it.
"""

from __future__ import annotations

import argparse
import json
import sys

import latency
import run


def backlog(logs, t: float) -> int:
    return sum(1 for r in logs if r.due is not None and r.due <= t
               and (r.first is None or r.first > t))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.HERE.parent / "src"))
    run.enable_compile_cache()
    cell = run.find_cell(run.HERE.parent, args.workload)
    clock = run.CompileClock()
    knee = None
    for rate in sorted(args.rates):
        cell.mix["rate_per_s"] = rate
        rec = run.serve(cell, args.seed, args.seconds, False,
                        clock=clock).rec
        ttft, missing = latency.ttfts(rec.logs, rec.t0, rec.t1, rec.t_end)
        q = rec.t0 + rec.window_s / 4
        before, after = backlog(rec.logs, q), backlog(rec.logs, rec.t1)
        print(json.dumps({
            "rate_per_s": rate, "due": len(ttft), "no_first_token": missing,
            "backlog_quarter": before, "backlog_close": after,
            "ttft_p50_ms": latency.percentile(ttft, 50) * 1e3,
            "ttft_p90_ms": latency.percentile(ttft, 90) * 1e3,
            "output_tok_s": latency.tokens_in(rec.logs, rec.t0, rec.t1)
            / rec.window_s}), flush=True)
        if after > before + 1 or missing:
            break
        knee = rate
    print(json.dumps({"knee_per_s": knee,
                      "rate_per_s": round(0.8 * knee, 3) if knee else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
