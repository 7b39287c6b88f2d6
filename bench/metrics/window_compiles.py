"""window_compiles: JAX backend compile requests inside the window
(``/jax/core/compile/backend_compile_duration`` events, persistent-cache
hits included)."""


def read(run):
    return run.compiles.compiles
