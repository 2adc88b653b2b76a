"""On-chip benchmark of the serving engine and the training executors.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` and prints one JSON
result line.  Everything a cell needs is found by name: its
configuration under ``bench/configs/``, its traffic mix under
``bench/traffic/``, the traffic's kind under ``bench/kinds/`` and each
per-layer metric's reader under ``bench/layer_metrics/``.
"""
