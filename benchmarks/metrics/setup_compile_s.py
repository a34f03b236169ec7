"""Backend-compile seconds jax reported during set-up (a cold run compiles
every program; a warm run reads them from the persistent cache)."""


def read(run):
    return float(run["setup_compile_s"])
