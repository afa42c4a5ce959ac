"""Device ms of the executor's prefill programs per prefill dispatch, in
the traced slice."""

import reads


def read(rec):
    if rec.trace is None:
        return None
    n = sum(1 for d in rec.traced if d["kind"] in ("prefill", "continue"))
    secs = rec.trace.module_seconds(*reads.PREFILL_PROGRAMS)
    return secs / n * 1e3 if n and secs else None
