"""Mean ms a request waited in the scheduler's queue before its first
admission, over the admissions in the window (the program's
``queue_wait_us / admitted``)."""


def read(rec):
    n = rec.counters.get("admitted", 0)
    return rec.counters.get("queue_wait_us", 0) / n / 1e3 if n else None
