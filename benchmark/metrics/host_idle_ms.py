"""Milliseconds a search in which the device idled while the host ran the
program's own code: the idle gaps of the traced window whose innermost
host span is one of the program's (``tts.*``: the warm-up, the program
lookup, the drain, the loop's bookkeeping under ``tts.search``), over the
window's searches.  A program without such spans gives nothing to read."""

PROGRAM = "tts."


def read(run):
    if run.trace is None or not run.searches:
        return None
    idle = [s for name, s in run.trace.gaps if name.startswith(PROGRAM)]
    return 1e3 * sum(idle) / len(run.searches) if idle else None
