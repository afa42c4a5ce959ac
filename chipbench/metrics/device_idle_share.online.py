"""Per cent of the traced slice in which no operation ran on the chip,
in an open-loop cell."""


def read(rec):
    if rec.trace is None:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)
