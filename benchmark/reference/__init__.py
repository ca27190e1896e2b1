"""The plain references that decide ``correct``.

One C program per problem, ``<problem>.c`` beside this file, written from
the published definitions and sharing no code, table or data with the
program under test.  Each is compiled at first use into
``benchmark/_build/`` (git-ignored), under a name keyed by its source's
hash, and prints one JSON object of the counts the program must match.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
from pathlib import Path


def binary(problem: str, root: Path) -> Path:
    src = root / "benchmark" / "reference" / f"{problem}.c"
    tag = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    exe = root / "benchmark" / "_build" / f"{problem}-{tag}"
    if not exe.exists():
        exe.parent.mkdir(exist_ok=True)
        tmp = exe.with_name(f"{exe.name}.{os.getpid()}.tmp")
        subprocess.run(["cc", "-O2", "-o", str(tmp), str(src)], check=True)
        os.replace(tmp, exe)
    return exe


def expected(cell, control: bool = False) -> dict:
    """The reference's counts for one search of ``cell``; with ``control``
    the reference's lower-precision variant, which must fail the check."""
    argv = [str(binary(cell.config["problem"], cell.root)),
            *cell.reference_argv()]
    if control:
        argv += ["--control", "1"]
    out = subprocess.run(argv, check=True, capture_output=True, text=True,
                         timeout=900)
    return json.loads(out.stdout)
