"""From the profiler's trace of a window to the device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; JAX reads it
itself (``jax.profiler.ProfileData``): planes, their lines, and events with
a name, a start and a duration in nanoseconds, all on one clock.

* The window is the span from the first ``bench.search`` host span (the
  harness's own ``TraceAnnotation``) to the end of the last one.
* Busy time is the union of the intervals of the device's operations (the
  ``XLA Ops`` line of each ``/device:TPU:<n>`` plane) inside the window,
  averaged over the chips used; idle is the rest of the window.
* Step time is the device time of the executions of the resident step
  program (``XLA Modules`` events whose name starts with ``jit_step``).
* Device operations are named by their HLO instruction and opcode (the
  event's name is the whole instruction text); ``%while`` is the resident
  loop and holds the cycle's own operations, such as the one-kernel cycle's
  ``custom-call``.
* Each idle gap is put down to what the host was doing at its midpoint:
  the innermost host span on the harness's window thread that covers it.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.search"
STEP_MODULE = "jit_step"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
TOP = 10


@dataclass
class Reduction:
    busy_s: float
    window_s: float
    step_s: float
    ops: list = field(default_factory=list)  # [(op name, seconds)], most first
    gaps: list = field(default_factory=list)  # [(host activity, seconds)]

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.ops[:TOP]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def merge(intervals) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals, as disjoint sorted ones."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


_OPCODE = re.compile(r"(?<![\w.%])([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(hlo: str) -> str:
    """``%body.3 custom-call tpu_custom_call`` for the instruction text
    ``%body.3 = (...) custom-call(...), custom_call_target="tpu_custom_call"``."""
    lhs, sep, rhs = hlo.partition(" = ")
    if not sep:
        return hlo
    parts = [lhs]
    opcode = _OPCODE.search(rhs)
    if opcode:
        parts.append(opcode.group(1))
    target = _TARGET.search(rhs)
    if target:
        parts.append(target.group(1))
    return " ".join(parts)


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def reduce_profile(profile, chips: int) -> Reduction:
    """The reduction of one trace (any object with ``planes``, ``lines``
    and ``events`` shaped as ``jax.profiler.ProfileData``'s)."""
    planes = list(profile.planes)
    host_spans = []  # (name, start, end) of every host event
    window_spans = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            evs = _events(line)
            if any(n == WINDOW_SPAN for n, _, _ in evs):
                host_spans.extend(evs)
                window_spans.extend(e for e in evs if e[0] == WINDOW_SPAN)
    if not window_spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0 = min(s for _, s, _ in window_spans)
    w1 = max(e for _, _, e in window_spans)

    devices = sorted((p for p in planes if p.name.startswith(DEVICE_PLANE)),
                     key=lambda p: p.name)[:chips]
    if not devices:
        raise ValueError("no TPU plane in the trace")
    busy_ns = step_ns = 0.0
    by_op: dict[str, float] = defaultdict(float)
    idle_by: dict[str, float] = defaultdict(float)
    for plane in devices:
        ops = []
        for line in plane.lines:
            evs = _events(line)
            if line.name == OPS_LINE:
                for name, a, b in evs:
                    for a2, b2 in clip([(a, b)], w0, w1):
                        ops.append((a2, b2))
                        by_op[op_name(name)] += b2 - a2
            elif line.name == MODULES_LINE:
                step_ns += sum(b - a for n, s, e in evs
                               if n.startswith(STEP_MODULE)
                               for a, b in clip([(s, e)], w0, w1))
        busy = merge(ops)
        busy_ns += sum(b - a for a, b in busy)
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle_by[host_activity(host_spans, (a + b) / 2)] += b - a
    n = len(devices)
    return Reduction(
        busy_s=busy_ns / n / 1e9, window_s=(w1 - w0) / 1e9,
        step_s=step_ns / n / 1e9,
        ops=sorted(((k, v / n / 1e9) for k, v in by_op.items()),
                   key=lambda kv: -kv[1]),
        gaps=sorted(((k, v / n / 1e9) for k, v in idle_by.items()),
                    key=lambda kv: -kv[1]),
    )


def host_activity(spans, t: float) -> str:
    """The innermost host span covering ``t`` (the latest to start)."""
    covering = [(s, n) for n, s, e in spans if s <= t < e]
    return max(covering)[1] if covering else "(no host span)"


def reduce(trace_dir: str, chips: int) -> Reduction:
    from jax.profiler import ProfileData

    paths = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    return reduce_profile(ProfileData.from_file(paths[0]), chips)
