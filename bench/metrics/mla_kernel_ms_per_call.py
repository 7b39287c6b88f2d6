"""mla_kernel_ms_per_call: the latent (MLA) paged-attention kernel's device
time in the trace over the tier's attention calls in the window
(``ServingTier.stats["attention_calls"]``, recorded by the driver).
Nothing to read without the kernel or the counter."""


def read(run):
    if run.trace is None:
        return None
    kernel_ns = run.trace.kernel_ns.get("mla_attention", 0.0)
    calls = getattr(run.window, "attention_calls", 0)
    if kernel_ns <= 0 or not calls:
        return None
    return kernel_ns / 1e6 / calls
