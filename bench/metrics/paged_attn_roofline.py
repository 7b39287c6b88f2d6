"""paged_attn_roofline: the paged-attention kernel's share of its roofline,
in %: the least time the chip could take for the window's kernel calls (the
larger of their bytes over peak HBM bandwidth and their operations over
peak FLOP/s, counted from shapes by ``bench/costs.py``) over the kernel's
device time in the trace. Nothing to read when the trace shows no kernel."""


def read(run):
    if run.trace is None:
        return None
    kernel_ns = run.trace.kernel_ns.get("paged_attention", 0.0)
    if kernel_ns <= 0:
        return None
    w, peaks = run.window, run.peaks
    least_s = max(w.bytes / peaks["hbm_bytes_per_s"],
                  w.flops / peaks["flops_per_s"])
    return 100.0 * least_s / (kernel_ns / 1e9)
