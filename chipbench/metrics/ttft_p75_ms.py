"""75th percentile of time to first token over every request due in the
window, from the time it was due (open loop), in ms."""

import latency


def read(rec):
    values, _ = latency.ttfts(rec.logs, rec.t0, rec.t1, rec.t_end)
    return latency.percentile(values, 75) * 1e3 if values else None
