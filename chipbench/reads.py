"""Arithmetic shared by the metric readers in ``metrics/``.

Each reader takes the run's ``Record`` (``run.py``) and returns a number
or ``None`` where there is nothing to read.  Device times come from the
trace reduction, and the work from the data-plane calls that started and
ended inside the traced slice (``rec.traced``); each such call blocks
until its device work is done, so its programs lie inside the slice.
"""

from __future__ import annotations

import workcount

#: jitted step programs of the executor, as the trace names them
PREFILL_PROGRAMS = ("_prefill_impl", "_continue_impl")
DECODE_PROGRAMS = ("_decode_multi_impl", "_decode_impl")


def decode_ms_per_step(rec) -> float | None:
    """Device ms of the decode programs per token-step (sum of horizons)."""
    if rec.trace is None:
        return None
    steps = sum(d["horizon"] for d in rec.traced if d["kind"] == "decode")
    secs = rec.trace.module_seconds(*DECODE_PROGRAMS)
    if not steps or not secs:
        return None
    return secs / steps * 1e3


def kernel_roofline(rec, needles: tuple[str, ...], kinds: tuple[str, ...],
                    work) -> float | None:
    """Per cent of its roofline that a kernel reached in the traced slice:
    least time for the work of the ``kinds`` calls (``work(cfg, call) ->
    (flops, bytes)``) over the summed device time of the ops matching
    ``needles``."""
    if rec.trace is None or not rec.peak:
        return None
    secs = rec.trace.op_seconds(*needles)
    calls = [d for d in rec.traced if d["kind"] in kinds]
    if not secs or not calls:
        return None
    flops = nbytes = 0.0
    for d in calls:
        f, b = work(rec.cfg, d)
        flops, nbytes = flops + f, nbytes + b
    return 100.0 * workcount.roofline_seconds(flops, nbytes, rec.peak) / secs
