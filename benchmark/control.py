#!/usr/bin/env python3
"""The control of a cell: the plain reference in a lower precision, put in
the program's place at the cell's own size.  The harness's check must call
it not correct; this prints the numbers compared beside their limits.

    python3 benchmark/control.py --workload <cell> --seed <n>

The benchmark's own runs never run it.  PFSP's control rounds every sum of
the bounds to bfloat16; N-Queens' keeps the rows in 4 bits (see
``reference/*.c``).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import reference, spec  # noqa: E402
from benchmark.run import compare, passes  # noqa: E402


def control(cell) -> dict:
    want = reference.expected(cell)
    t = time.perf_counter()
    got = reference.expected(cell, control=True)
    record = {**got, "complete": True, "seconds": time.perf_counter() - t}
    checks = compare([record], want, started=1)
    return {"correct": passes(checks), "reference": want, "control": got,
            "checks": checks}


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    out = control(spec.cell(args.workload, root))
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed, **out}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
