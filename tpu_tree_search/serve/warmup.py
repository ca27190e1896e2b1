"""AOT warm matrix — ``scripts/warm_cache.py`` promoted to a module.

Two consumers share the one config table:

  * ``tts warmup`` (and the legacy script, now a shim) runs each config
    in a subprocess against the persistent XLA compile cache
    (`utils/compile_cache.py`), reporting per-config **hit/miss**: a
    miss banks new cache files, a hit compiles nothing — the count of new
    files in the cache directory is the measurement, so a second run of
    the same matrix must report all hits.
  * ``tts serve --warm`` admits the serve-able configs as internal
    ``max_steps=1`` jobs, warming the daemon's OWN program pool in
    process — after it, the first tenant job of a warmed class admits
    with zero recompiles.

Cache keys include the full program shape, so warming MUST run the exact
entry points with the exact shapes the consumers use: each config is one
``resident_search(..., max_steps=1)`` — the full while-loop program plus
its kernels, compiled and executed for a single step. Staged and unstaged
lb2 are distinct programs; both warm. Each subprocess has its own timeout
(a compile hang must only cost its slot — bench.py's probe lesson).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

_ITEM = r"""
import os, time, sys
t0 = time.time()
from tpu_tree_search.engine.resident import resident_search
from tpu_tree_search.problems import NQueensProblem, PFSPProblem
from tpu_tree_search.utils import compile_cache

compile_cache.enable()
kind = sys.argv[1]
if kind == "kernel":
    # Kernel-level warm at the smoke-gate shapes: large-instance resident
    # programs explore tens of millions of nodes in ONE K=4096 dispatch
    # (max_steps can't cut inside a dispatch), blowing the slot timeout on
    # execution the cache doesn't need — the session's reusable artifacts
    # for these classes are the Mosaic KERNEL compiles.
    import jax.numpy as jnp
    from tpu_tree_search.ops import pallas_kernels as PK
    inst, lb, B = int(sys.argv[2]), sys.argv[3], int(sys.argv[4])
    prob = PFSPProblem(inst=inst, lb=lb, ub=1)
    t = prob.device_tables()
    n = prob.jobs
    prmu = jnp.tile(jnp.arange(n, dtype=jnp.int32), (B, 1))
    limit1 = jnp.zeros((B,), dtype=jnp.int32)
    if lb == "lb1":
        out = PK.pfsp_lb1_bounds(prmu, limit1, t.ptm_t, t.min_heads,
                                 t.min_tails)
    else:
        out = PK.pfsp_lb2_bounds(prmu, limit1, t)
    out.block_until_ready()
    print(f"WARM_OK shape={tuple(out.shape)} wall={time.time() - t0:.1f}s")
    sys.exit(0)
if kind == "nqueens":
    prob = NQueensProblem(N=int(sys.argv[2]))
else:
    prob = PFSPProblem(inst=int(sys.argv[2]), lb=sys.argv[3], ub=1)
M = int(sys.argv[3] if kind == "nqueens" else sys.argv[5])
res = resident_search(prob, m=25, M=M, max_steps=1)
print(f"WARM_OK tree={res.explored_tree} wall={time.time() - t0:.1f}s")
"""


class WarmConfig:
    """One warm slot: a name (CLI-selectable), the subprocess argv tail,
    env overrides, and — when the config is a full resident run the serve
    daemon can replay — the equivalent job spec."""

    def __init__(self, name: str, label: str, argv: list[str],
                 env: dict | None = None):
        self.name = name
        self.label = label
        self.argv = argv
        self.env = env or {}

    @property
    def servable(self) -> bool:
        return self.argv[0] != "kernel"

    def spec(self) -> dict | None:
        """The serve-side job spec for this config (``max_steps=1``), or
        None for kernel-only rows. Env-only knobs (TTS_K, TTS_COMPACT,
        TTS_LB2_PAIRBLOCK) map to spec fields; staging env rows have no
        spec knob and warm under the daemon's own TTS_LB2_STAGED."""
        if not self.servable:
            return None
        kind = self.argv[0]
        spec: dict = {"tier": "device", "max_steps": 1,
                      "label": f"warm:{self.name}"}
        if kind == "nqueens":
            spec.update(problem="nqueens", N=int(self.argv[1]),
                        M=int(self.argv[2]))
        else:
            spec.update(problem="pfsp", inst=int(self.argv[1]),
                        lb=self.argv[2], ub=1, M=int(self.argv[4]))
        if "TTS_K" in self.env:
            spec["K"] = int(self.env["TTS_K"])
        if "TTS_COMPACT" in self.env:
            spec["compact"] = self.env["TTS_COMPACT"]
        if "TTS_LB2_PAIRBLOCK" in self.env:
            pb = self.env["TTS_LB2_PAIRBLOCK"]
            spec["lb2_pairblock"] = pb if pb == "auto" else int(pb)
        return spec


# The bench + smoke-gate matrix, most valuable first so a cut-short warm
# still banks the flagship programs. M values match the bench's
# measured defaults (HEADLINE_M / lb2_M — scripts/headline_tune.py,
# scripts/lb2_tune.py): warming MUST compile the exact programs the bench
# dispatches. See scripts/warm_cache.py history for the per-row rationale
# (staged/unstaged lb2 pairs, the TTS_K=auto ladder rungs, compaction-mode
# A/B variants, large-instance kernel-only rows).
CONFIGS: list[WarmConfig] = [
    WarmConfig("ta014-lb2-staged", "ta014 lb2 staged M=1024",
               ["pfsp", "14", "lb2", "-", "1024"], {"TTS_LB2_STAGED": "1"}),
    WarmConfig("ta014-lb2-unstaged", "ta014 lb2 unstaged M=1024",
               ["pfsp", "14", "lb2", "-", "1024"], {"TTS_LB2_STAGED": "0"}),
    WarmConfig("ta014-lb2-staged-pb1", "ta014 lb2 staged M=1024 pairblock=1",
               ["pfsp", "14", "lb2", "-", "1024"],
               {"TTS_LB2_STAGED": "1", "TTS_LB2_PAIRBLOCK": "1"}),
    WarmConfig("ta021-lb2-staged", "ta021 lb2 staged M=1024",
               ["pfsp", "21", "lb2", "-", "1024"], {"TTS_LB2_STAGED": "1"}),
    WarmConfig("ta021-lb2-unstaged", "ta021 lb2 unstaged M=1024",
               ["pfsp", "21", "lb2", "-", "1024"], {"TTS_LB2_STAGED": "0"}),
    WarmConfig("ta014-lb1-jnp", "ta014 lb1 M=1024 jnp",
               ["pfsp", "14", "lb1", "-", "1024"], {"TTS_PALLAS": "0"}),
    WarmConfig("ta014-lb1-K1", "ta014 lb1 M=1024 K=1",
               ["pfsp", "14", "lb1", "-", "1024"], {"TTS_K": "1"}),
    WarmConfig("ta014-lb1-K4", "ta014 lb1 M=1024 K=4",
               ["pfsp", "14", "lb1", "-", "1024"], {"TTS_K": "4"}),
    WarmConfig("ta014-lb1-K16", "ta014 lb1 M=1024 K=16",
               ["pfsp", "14", "lb1", "-", "1024"], {"TTS_K": "16"}),
    WarmConfig("ta014-lb1-K64", "ta014 lb1 M=1024 K=64",
               ["pfsp", "14", "lb1", "-", "1024"], {"TTS_K": "64"}),
    WarmConfig("ta014-lb1-K256", "ta014 lb1 M=1024 K=256",
               ["pfsp", "14", "lb1", "-", "1024"], {"TTS_K": "256"}),
    WarmConfig("ta014-lb1-K1024", "ta014 lb1 M=1024 K=1024",
               ["pfsp", "14", "lb1", "-", "1024"], {"TTS_K": "1024"}),
    WarmConfig("ta014-lb1", "ta014 lb1 M=1024",
               ["pfsp", "14", "lb1", "-", "1024"]),
    WarmConfig("ta014-lb1d", "ta014 lb1_d M=1024",
               ["pfsp", "14", "lb1_d", "-", "1024"]),
    WarmConfig("nqueens-15", "nqueens N=15 M=65536",
               ["nqueens", "15", "65536"]),
    WarmConfig("nqueens-16", "nqueens N=16 M=65536",
               ["nqueens", "16", "65536"]),
    WarmConfig("nqueens-17", "nqueens N=17 M=65536",
               ["nqueens", "17", "65536"]),
    WarmConfig("nqueens-15-M8k", "nqueens N=15 M=8192",
               ["nqueens", "15", "8192"]),
    WarmConfig("nqueens-15-M256k", "nqueens N=15 M=262144",
               ["nqueens", "15", "262144"]),
    WarmConfig("nqueens-16-M256k", "nqueens N=16 M=262144",
               ["nqueens", "16", "262144"]),
    WarmConfig("nqueens-17-M128k", "nqueens N=17 M=131072",
               ["nqueens", "17", "131072"]),
    WarmConfig("ta014-lb1-scatter", "ta014 lb1 M=1024 compact=scatter",
               ["pfsp", "14", "lb1", "-", "1024"],
               {"TTS_COMPACT": "scatter"}),
    WarmConfig("ta014-lb1-sort", "ta014 lb1 M=1024 compact=sort",
               ["pfsp", "14", "lb1", "-", "1024"], {"TTS_COMPACT": "sort"}),
    WarmConfig("ta014-lb1-search", "ta014 lb1 M=1024 compact=search",
               ["pfsp", "14", "lb1", "-", "1024"],
               {"TTS_COMPACT": "search"}),
    WarmConfig("ta014-lb2-scatter", "ta014 lb2 M=1024 compact=scatter",
               ["pfsp", "14", "lb2", "-", "1024"],
               {"TTS_COMPACT": "scatter"}),
    WarmConfig("ta014-lb2-sort", "ta014 lb2 M=1024 compact=sort",
               ["pfsp", "14", "lb2", "-", "1024"], {"TTS_COMPACT": "sort"}),
    WarmConfig("ta014-lb2-search", "ta014 lb2 M=1024 compact=search",
               ["pfsp", "14", "lb2", "-", "1024"],
               {"TTS_COMPACT": "search"}),
    WarmConfig("nqueens-15-scatter", "nqueens N=15 M=65536 compact=scatter",
               ["nqueens", "15", "65536"], {"TTS_COMPACT": "scatter"}),
    WarmConfig("ta031-lb1-kernel", "ta031 lb1 kernel B=64",
               ["kernel", "31", "lb1", "64"]),
    WarmConfig("ta056-lb1-kernel", "ta056 lb1 kernel B=32",
               ["kernel", "56", "lb1", "32"]),
    WarmConfig("ta056-lb2-kernel", "ta056 lb2 kernel B=16",
               ["kernel", "56", "lb2", "16"]),
    WarmConfig("ta111-lb1-kernel", "ta111 lb1 kernel B=16",
               ["kernel", "111", "lb1", "16"]),
]


def select_configs(names: str | None) -> list[WarmConfig]:
    """``names``: None/"all" for the whole matrix, "serve" for the
    serve-able subset, else a comma-separated name list (unknown names
    raise ValueError — a typo must not silently warm nothing)."""
    if names in (None, "", "all"):
        return list(CONFIGS)
    if names == "serve":
        return [c for c in CONFIGS if c.servable]
    by_name = {c.name: c for c in CONFIGS}
    out = []
    unknown = []
    for name in names.split(","):
        name = name.strip()
        if name in by_name:
            out.append(by_name[name])
        elif name:
            unknown.append(name)
    if unknown:
        raise ValueError(
            f"unknown warm config(s): {', '.join(unknown)} "
            f"(known: {', '.join(sorted(by_name))})"
        )
    return out


def _cache_files(path: str) -> set[str]:
    if not os.path.isdir(path):
        return set()
    out = set()
    for root, _dirs, files in os.walk(path):
        for f in files:
            out.add(os.path.join(root, f))
    return out


def run_configs(configs: list[WarmConfig], timeout_s: float | None = None,
                emit=print) -> int:
    """The subprocess warm loop (``tts warmup`` / the legacy script):
    returns the failure count. Per config, reports ok/FAIL, wall seconds,
    and the compile-cache delta — ``miss(+N files)`` banked N new
    executables, ``hit`` compiled nothing new (the warm goal)."""
    if timeout_s is None:
        timeout_s = float(os.environ.get("TTS_WARM_TIMEOUT", "420"))
    # The parent never touches JAX: each config's child holds the chip
    # alone, one after another.
    from ..utils import compile_cache

    cdir = compile_cache.cache_dir()
    failures = 0
    for cfg in configs:
        before = _cache_files(cdir)
        t0 = time.time()
        try:
            res = subprocess.run(
                [sys.executable, "-c", _ITEM, *cfg.argv],
                timeout=timeout_s, capture_output=True, text=True,
                env={**os.environ, **cfg.env},
            )
            ok = res.returncode == 0 and "WARM_OK" in res.stdout
            detail = (res.stdout.strip().splitlines() or [""])[-1] if ok else \
                (res.stderr or res.stdout).strip().splitlines()[-1:]
        except subprocess.TimeoutExpired:
            ok, detail = False, f"timeout {timeout_s:.0f}s"
        failures += not ok
        new = len(_cache_files(cdir) - before)
        cache = f"miss(+{new} files)" if new else "hit"
        # flush: the log must stream per-config progress (a redirect
        # block-buffers prints, hiding everything until exit).
        emit(f"{'ok ' if ok else 'FAIL'} {time.time() - t0:7.1f}s  "
             f"[{cache}]  {cfg.name}  {detail}")
    return failures


def warmup_main(names: str | None = None,
                timeout_s: float | None = None) -> int:
    """``tts warmup`` entry point."""
    try:
        configs = select_configs(names)
    except ValueError as e:
        print(f"Error: {e}", file=sys.stderr)
        return 2
    failures = run_configs(configs,
                           timeout_s=timeout_s,
                           emit=lambda line: print(line, flush=True))
    return 1 if failures else 0


def warm_pool(daemon, names: str | None = "serve"):
    """``tts serve --warm``: admit each serve-able config as an internal
    max_steps=1 job and wait, warming the daemon's program pool so the
    first real job of each class is a zero-recompile admission. Yields one
    progress line per config (the daemon prints them)."""
    configs = [c for c in select_configs(names or "serve") if c.servable]
    for cfg in configs:
        spec = cfg.spec()
        payload, code = daemon.submit(spec)
        if code != 201:
            yield (f"warm FAIL {cfg.name}: {payload.get('error')}")
            continue
        job = daemon.registry.get(payload["id"])
        t0 = time.time()
        while job.state not in ("done", "failed", "cancelled"):
            time.sleep(0.1)
        state = "ok " if job.state == "done" else "FAIL"
        yield (f"warm {state} {time.time() - t0:6.1f}s  {cfg.name}  "
               f"class={job.class_key}")
