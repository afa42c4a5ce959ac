"""Per cent of its roofline reached by the paged decode attention kernel
(``kernels/paged_attention.py``) in the traced slice, in an open-loop
cell."""

import reads
import workcount


def read(rec):
    return reads.kernel_roofline(
        rec, ("paged_decode_attention",), ("decode",),
        lambda cfg, d: workcount.decode_attention(cfg, d["ctxs"]))
