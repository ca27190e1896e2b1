"""Share of search time in the host phases: phase 1 (warm-up to m nodes)
plus phase 3 (drain of the residual pool), over the whole search, summed
over the window's searches (``SearchResult.phases``, host clock)."""


def read(run):
    total = sum(s["seconds"] for s in run.searches)
    host = sum(s["phases"][0][0] + s["phases"][-1][0] for s in run.searches)
    return 100.0 * host / total
