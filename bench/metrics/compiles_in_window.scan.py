"""Programs JAX compiled or loaded inside the window (its monitoring
event ``/jax/core/compile/backend_compile_duration``); should be 0."""


def read(run):
    return run.compiles_in_window
