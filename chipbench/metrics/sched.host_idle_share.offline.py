"""Per cent of the traced slice in which no operation ran on the chip
while the host was in the scheduler's own bookkeeping, in a closed-loop
cell: the idle seconds whose innermost host event is one of the
program's ``serve.sched.*`` spans, over the slice."""


def read(rec):
    if rec.trace is None:
        return None
    idle = sum(sec for name, sec in rec.trace.idle_by_host.items()
               if name.startswith("serve.sched."))
    return 100.0 * idle / rec.trace.window_s
