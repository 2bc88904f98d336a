"""On-chip benchmark of the served GCN: one command, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` (a configuration
from ``configs/``, whose model is a file of ``models/``, and a traffic
mix from ``traffic/``) and prints one JSON result line. Each metric is
a reader in ``metrics/``, found by name.
"""
