"""Milliseconds a search in which the device idled under the runtime calls
the search issues: the idle gaps of the traced window whose innermost
host span is JAX's own (``DevicePut``, ``np.asarray(jax.Array)``,
``PjitFunction(...)``: uploads, scalar and residual reads, eager ops),
that is neither the program's (``tts.*``), nor the harness's
(``bench.*``), nor no span at all, over the window's searches."""

NOT_RUNTIME = ("tts.", "bench.", "(no host span)")


def read(run):
    if run.trace is None or not run.searches:
        return None
    idle = sum(s for name, s in run.trace.gaps
               if not name.startswith(NOT_RUNTIME))
    return 1e3 * idle / len(run.searches)
