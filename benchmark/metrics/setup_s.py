"""Process start to window start: JAX start, native runtime, problem, and
the cell's programs compiled or loaded from the compile cache."""


def read(run):
    return run.setup_s
