"""Mean fused decode horizon K of the scheduler over the window
(``decode_horizon / decode_dispatches``, the program's counters)."""


def read(rec):
    n = rec.counters.get("decode_dispatches", 0)
    return rec.counters.get("decode_horizon", 0) / n if n else None
