"""setup_s: seconds from process start to the window's start: JAX's
start, the seeded fields, the model fields, placement, compilation or
the cache's load, and the warm-up cycle (host clock)."""


def read(run):
    return run.setup_s
