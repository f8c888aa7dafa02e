"""On-chip benchmark of the co-execution runtime.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything a
cell names is found by name under this directory: its configuration in
``configs/<config>.json`` (with the plain reference named there in
``reference/``), its traffic mix in ``traffic/<traffic>.json``, each
metric's reader in ``metrics/<metric>.py`` and each kernel's operations
and bytes in ``work/<kernel>.py``.
"""
