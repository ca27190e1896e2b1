"""Window seconds per completed search: the time to a proven optimum or a
complete count."""


def read(run):
    return run.window_s / len(run.searches)
