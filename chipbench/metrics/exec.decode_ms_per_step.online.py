"""Device ms of the executor's decode programs per token-step (sum of
fused horizons), in the traced slice of an open-loop cell."""

import reads


def read(rec):
    return reads.decode_ms_per_step(rec)
