#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up: start JAX and find the chips the cell asks for (no TPU: exit 2, no
result), load the native runtime, build one problem object and warm up the
cell's own programs with one search, from JAX's compile cache at
``benchmark/_build/jax_cache`` (given to the program through
``JAX_COMPILATION_CACHE_DIR``, which ``utils/compile_cache.py`` then leaves
alone), so that only a checkout's first run compiles.  Window: whole searches on that one
object, back to back, through the CLI's ``--tier device`` path
(``cli.run_tier``), started while less than ``--seconds`` have passed; it
ends when the last search it started ends.  Check: every search of the
window against the plain reference (``benchmark/reference/``), run after
the window.  With ``--trace 1`` the window runs under the profiler and the
run reports the per-layer metrics instead of the end-to-end ones.

The cell's data is the published instance itself, so ``--seed`` changes
nothing that is searched: see PERF.md, section 4.  The last line of standard
output is the result object; the numbers compared and their limits are the
last lines of standard error and the last key of that object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import reference, spec, xplane  # noqa: E402
from benchmark.compile_clock import CompileClock  # noqa: E402

#: JAX's persistent compile cache (git-ignored with benchmark/_build).
CACHE_DIR = ROOT / "benchmark" / "_build" / "jax_cache"

#: Seconds past the window's close that the harness waits for a search
#: it started before it counts that search as never finished.
GRACE_S = 60.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def tpu_devices(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs


@dataclass
class Run:
    """What a metric reader (``benchmark/metrics/<name>.py``) reads."""

    setup_s: float
    window_s: float
    searches: list  # one record per search that finished in the window
    compile_setup: dict  # CompileClock deltas: set-up, window
    compile_window: dict
    trace: object = None  # xplane.Reduction of the traced window, or None


def search_record(res, seconds: float) -> dict:
    return {
        "tree": res.explored_tree, "sol": res.explored_sol, "best": res.best,
        "complete": res.complete, "seconds": seconds,
        "phases": [[p.seconds, p.tree, p.sol] for p in res.phases],
    }


def routing(res) -> dict:
    return {"megakernel": res.megakernel, "megakernel_mt": res.megakernel_mt,
            "megakernel_tiled": res.megakernel_tiled, "compact": res.compact,
            "kernel_backend": res.kernel_backend,
            "kernel_interpret": res.kernel_interpret, "K": res.k_resolved}


class Window(threading.Thread):
    """Starts whole searches while less than ``seconds`` have passed."""

    def __init__(self, search, seconds: float):
        super().__init__(name="bench-window", daemon=True)
        self.search = search
        self.seconds = seconds
        self.started = 0
        self.done: list[dict] = []
        self.first = None  # the first search's SearchResult (routing)
        self.error: str | None = None
        self.t0 = self.t_end = None

    def run(self):
        import jax

        try:
            self.t0 = time.perf_counter()
            while time.perf_counter() - self.t0 < self.seconds:
                self.started += 1
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.search"):
                    res = self.search()
                t_done = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.between"):
                    self.first = self.first or res
                    self.done.append(search_record(res, t_done - t))
            self.t_end = time.perf_counter()
        except Exception:
            self.error = traceback.format_exc()


def compare(searches: list[dict], want: dict, started: int) -> dict:
    """The numbers compared, each beside its limit: the widest gap of each
    count over the window's searches, and the searches that never ended
    or did not complete."""
    checks = {}
    for key, ref in want.items():
        gaps = [abs(s[key] - ref) for s in searches]
        checks[f"{key}_gap"] = {"value": max(gaps, default=0), "limit": 0}
    lost = started - sum(s["complete"] for s in searches)
    checks["searches_lost"] = {"value": lost, "limit": 0}
    return checks


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def main(argv=None, find_chips=tpu_devices, grace_s: float = GRACE_S,
         root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, root)
    # JAX's persistent compile cache at a fixed path inside the checkout,
    # whatever the machine sets: the path is part of the cache's key, and
    # two checkouts on one machine must share nothing.  Read by JAX when
    # it is imported (find_chips), and then left alone by the program's
    # utils/compile_cache.enable().  Not the program's own default
    # (<checkout>/.jax_cache), which CPU test runs fill with entries that
    # broke every write of the cache on the chip (PERF.md, section 6).
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)

    try:
        devices = find_chips(cell.chips)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    import jax

    used = devices[:cell.chips]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices)}
    emit({"device": device})

    import tpu_tree_search

    pkg = Path(tpu_tree_search.__file__).resolve().parent
    if pkg.parent != ROOT:
        print(f"benchmark: imported tpu_tree_search from {pkg}, not from "
              f"{ROOT}", file=sys.stderr, flush=True)
        return 1
    from tpu_tree_search import cli, native
    from tpu_tree_search.utils import compile_cache

    cache = compile_cache.enable()
    clock = CompileClock()
    if native.load() is None:
        print(f"benchmark: native runtime did not load: {native.load_error()}",
              file=sys.stderr, flush=True)
        return 1

    parser = cli.build_parser()
    run_args = parser.parse_args(cell.program_argv())
    cli.validate_args(parser, run_args)
    problem = cli.make_problem(run_args)
    window = Window(lambda: cli.run_tier(problem, copy.copy(run_args)),
                    args.seconds)
    try:
        cli.run_tier(problem, copy.copy(run_args))
    except Exception:  # the program failed before the window: no answer
        window.error = traceback.format_exc()
        window.started = 1
    setup_done = clock.snapshot()
    setup_s = time.perf_counter() - T_START
    emit({"setup_s": setup_s, "compile_cache": cache,
          "compile": setup_done, "argv": cell.program_argv()})

    trace_dir = None
    if args.trace and not window.error:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    if not window.error:
        window.start()
        window.join(args.seconds + grace_s)
    hung = window.is_alive()
    if trace_dir and not hung:
        jax.profiler.stop_trace()
    in_window = CompileClock.delta(clock.snapshot(), setup_done)
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)

    searches = list(window.done)
    if window.first is not None:
        emit({"routing": routing(window.first)})
    if window.error:
        print(window.error, file=sys.stderr, flush=True)
    problem_name = problem.name
    window.search = None
    del problem
    gc.collect()

    want = reference.expected(cell)
    for i, s in enumerate(searches):
        emit({"search": i, **{k: s[k] for k in ("tree", "sol", "best",
                                                "seconds", "phases")},
              "reference": want})
    checks = compare(searches, want, window.started)
    window_s = ((window.t_end or time.perf_counter()) - window.t0
                if window.t0 is not None else 0.0)
    emit({"window_s": window_s, "searches": len(searches),
          "attempted": window.started, "window_compile": in_window,
          "problem": problem_name})

    run = Run(setup_s=setup_s, window_s=window_s, searches=searches,
              compile_setup=setup_done, compile_window=in_window)
    result = {"correct": passes(checks) and not hung and not window.error,
              "attempted": window.started,
              "failed": window.started - sum(
                  all(s[k] == v for k, v in want.items()) for s in searches)}
    if trace_dir and not hung:
        run.trace = xplane.reduce(trace_dir, len(used))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    metrics = {}
    for m in cell.per_layer if args.trace else cell.end_to_end:
        value = spec.reader(m["name"], root)(run) if searches else None
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device, seed=args.seed,
                  searches=len(searches),
                  window_compiles=in_window["compiles"])
    if run.trace is not None:
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    emit(result)
    return 0


if __name__ == "__main__":
    rc = main()
    if any(t.name == "bench-window" for t in threading.enumerate()):
        # A search that never returned still holds its thread (and the
        # device): leave without waiting for it.
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(rc)
    sys.exit(rc)
