"""Device time of the program's named kernels in the traced window over the
nodes its phase 2 explored.  The kernels are every ``%tts_*`` custom call
on the ``XLA Ops`` line: the one-kernel cycle ``tts_cycle_<family>``
(``ops/megakernel.py``) and the bound kernels ``tts_eval_<family>``
(``ops/pallas_kernels.py``).  A program whose kernels carry no such name
gives nothing to read."""

KERNELS = "%tts_"


def read(run):
    if run.trace is None:
        return None
    kernel_s = sum(s for name, s in run.trace.ops if name.startswith(KERNELS))
    nodes = sum(s["phases"][1][1] for s in run.searches)
    return 1e9 * kernel_s / nodes if kernel_s and nodes else None
