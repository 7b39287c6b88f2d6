"""attend_host_ms_per_step: host time of the ``serve.attend`` spans (the
per-layer ``ServingTier.attend`` calls: block tables, restores, kernel
dispatch and the wait for its result) per engine step of the window."""


def read(run):
    t = run.spans.seconds.get("serve.attend")
    steps = len(run.window.steps)
    return 1e3 * t / steps if t and steps else None
