"""Programs compiled or loaded from the compile cache inside the window
(``/jax/core/compile/backend_compile_duration`` events).  Warm-up should
leave this at 0; the names are printed on standard error."""


def read(run):
    return run.compiles_in_window
