"""Device time of the resident step program in the traced window over the
nodes its phase 2 explored."""


def read(run):
    if run.trace is None or not run.trace.step_s:
        return None
    nodes = sum(s["phases"][1][1] for s in run.searches)
    return 1e9 * run.trace.step_s / nodes if nodes else None
