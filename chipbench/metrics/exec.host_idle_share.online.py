"""Per cent of the traced slice in which no operation ran on the chip
while the host was in the executor, in an open-loop cell: the idle
seconds whose innermost host event is one of the program's
``serve.exec.*`` spans or a JAX runtime event (dispatch, argument
transfer, read back to the host), over the slice."""

#: host events of the executor's data-plane calls
NAMES = ("serve.exec.", "PjitFunction", "DevicePut", "shard_args",
         "np.asarray", "ParseArguments")


def read(rec):
    if rec.trace is None:
        return None
    idle = sum(sec for name, sec in rec.trace.idle_by_host.items()
               if name.startswith(NAMES))
    return 100.0 * idle / rec.trace.window_s
