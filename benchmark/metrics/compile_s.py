"""Backend compile seconds during set-up, from JAX's monitoring events."""


def read(run):
    return run.compile_setup["compile_s"]
