"""Share of the window in which no operation ran on the device, from the
profiler trace (busy time is the union of the ``XLA Ops`` intervals)."""


def read(run):
    busy = run.device.busy_s(run.t0, run.t_end)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / run.window_s)
