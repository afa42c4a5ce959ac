"""Per cent of the chip's bf16 peak: model FLOPs of the real tokens that
the data-plane calls inside the traced slice processed (``workcount.
model_flops``) over the slice's length times the peak, in a closed-loop
cell."""

import workcount


def read(rec):
    if rec.trace is None or not rec.traced or not rec.peak:
        return None
    flops = sum(workcount.model_flops(rec.cfg, d) for d in rec.traced)
    return 100.0 * flops / (rec.trace.window_s * rec.peak["flops_per_s"])
