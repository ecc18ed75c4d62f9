"""The on-chip benchmark of the graph engine.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON result line.  Everything that belongs to one configuration, traffic
mix or per-layer metric sits in a file of its own under this directory
(``configs/``, ``traffic/``, ``layer_metrics/``), found by the name
``BENCHMARK.json`` gives it, and each algorithm a traffic mix names has
its module in ``algorithms/``.  Nothing here is imported by the program
under test; the benchmark imports the program's public entry points.
"""
