"""XLA compilations inside the window (jax.monitoring; persistent-cache hits
included): a shape the warm-up missed. Should read 0. The suffix only says
which end-to-end metric a compile stalls in that cell (``.serve``,
``.decode``); the count is the same."""


def read(run, suffix=""):
    return run.compiles_in_window
