"""Output tokens committed inside the window per second of the window."""

import latency


def read(rec):
    return latency.tokens_in(rec.logs, rec.t0, rec.t1) / rec.window_s
