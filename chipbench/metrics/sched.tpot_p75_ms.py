"""75th percentile over requests of the mean gap between consecutive
tokens committed inside the window, in ms: the time per output token that
the scheduler's horizons and prefill stalls give (``latency.tpots``)."""

import latency


def read(rec):
    values = latency.tpots(rec.logs, rec.t0, rec.t1)
    return latency.percentile(values, 75) * 1e3 if values else None
