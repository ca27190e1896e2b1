"""Device time of the resident step outside its named kernels, over the
nodes phase 2 explored: the step program's time (``jit_step``) less that
of every ``%tts_*`` custom call (the pop slices, the push stitch and the
loop control of ``engine/resident.py`` ``loop_fns``).  With
``kernel_ns_per_node`` it adds up to ``step_ns_per_node``."""

KERNELS = "%tts_"


def read(run):
    if run.trace is None or not run.trace.step_s:
        return None
    kernel_s = sum(s for name, s in run.trace.ops if name.startswith(KERNELS))
    nodes = sum(s["phases"][1][1] for s in run.searches)
    if not kernel_s or not nodes:
        return None
    return 1e9 * (run.trace.step_s - kernel_s) / nodes
