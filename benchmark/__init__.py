"""The on-chip benchmark: harness, yardstick and data files.

``python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints the
contract's result object as its last stdout line.  Everything that belongs
to one configuration, one traffic mix or one metric is a file of its own,
found by the name the manifest gives (``manifest.py``); the harness takes
from the program only the system under test, its spans and its counters.
"""
