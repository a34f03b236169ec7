"""Backend compilations jax reported inside the window.  Any makes the run
``correct: false``."""


def read(run):
    return float(run["compiles_in_window"])
