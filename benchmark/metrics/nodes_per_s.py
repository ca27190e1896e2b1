"""Explored nodes of all the window's searches over the window's seconds."""


def read(run):
    return sum(s["tree"] for s in run.searches) / run.window_s
