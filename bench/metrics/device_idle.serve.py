"""device_idle.serve: share of the traced window, in %, in which no
operation ran on a device, averaged over the devices in use."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0 or not run.trace.busy_ns:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
