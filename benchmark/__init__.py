"""The benchmark: whole searches, back to back, on the chip.

Everything here is the yardstick and lives apart from the program, so that a
change to the program cannot move it: ``run.py`` (the harness), ``spec.py``
(cells, configurations and traffic found by name), ``compile_clock.py``,
``xplane.py`` (trace to numbers), ``reference/`` (plain implementations that
decide ``correct``), ``metrics/`` (one reader per metric), ``configs/`` and
``traffic/`` (data).  ``python3 benchmark/run.py --help`` runs one cell.
"""
