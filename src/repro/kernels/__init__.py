"""Pallas kernels for the device half of the system.

The platform, not the caller, decides how a kernel runs: compiled on the
TPU, interpreted on the CPU (where the tests run). There is no silent
fallback: any other platform has no path for these kernels and raises.
"""


def interpret_mode() -> bool:
    """True when Pallas kernels must run in the interpreter (CPU), False when
    they compile (TPU)."""
    import jax
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for platform {platform!r}")
