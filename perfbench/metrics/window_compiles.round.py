"""Backend-compile events inside the window, from jax.monitoring: a round
cell should read 0."""


def read(run):
    return run.window_compiles
