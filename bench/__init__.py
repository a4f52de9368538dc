"""The benchmark: host seconds per simulated training iteration.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything a cell needs
is found by name: ``configs/<config>.json``, ``traffic/<mix>.json``,
``limits/<cell>.json`` and ``metrics/<metric>.py``.  The plain references
that decide ``correct`` live in ``reference/`` and import nothing of the
program.
"""
