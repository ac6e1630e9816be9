"""Set-up: process start to the window's opening (JAX start-up, planning,
weights from the seed, bringing up the backend, compiling or loading from
the persistent cache, and running every program once)."""


def read(run):
    return run.setup_s
