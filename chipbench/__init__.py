"""Chip benchmark of the what-if engine.

One command runs one cell of ``BENCHMARK.json`` once::

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: its configuration in
``configs/<config>.json`` and that configuration's plain reference in
``references/<config>.py``, its traffic mix in ``traffic/<traffic>.json``
and the mix's driver in ``drivers/<mode>.py`` (what every driver shares
is in :mod:`chipbench.load`), and each metric's reader in
``metrics/<metric>.py``.  A new deployment's cell is new files and new
``BENCHMARK.json`` entries only.
"""
