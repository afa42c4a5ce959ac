"""Per cent of its roofline reached by the flash prefill attention kernel
(``kernels/flash_attention.py``) in the traced slice."""

import reads
import workcount


def read(rec):
    return reads.kernel_roofline(
        rec, ("flash_attention",), ("prefill",),
        lambda cfg, d: workcount.flash_prefill(cfg, d["lens"]))
