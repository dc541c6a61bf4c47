"""Chip benchmark of the what-if engine.

One command runs one cell of ``BENCHMARK.json`` once::

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: its configuration in
``configs/<config>.json``, its traffic mix in ``traffic/<traffic>.json``
(read by the one generator in :mod:`chipbench.load`) and each metric's
reader in ``metrics/<metric>.py``.
"""
