"""pool_ops_device_ms_per_token: device time of every operation in the
traced window that is not a kernel (pool copies on page writes, layer
slices, gathers), summed over devices, per token committed."""


def read(run):
    if run.trace is None or not run.trace.op_ns or not run.window.tokens:
        return None
    return run.trace.non_kernel_ns() / 1e6 / run.window.tokens
