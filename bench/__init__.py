"""Chip benchmark of the DeltaKWS serving path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the machine it is started on.
Everything that belongs to one configuration, traffic mix, serving driver
or per-layer metric lives in a file of its own that the harness finds by
the name ``BENCHMARK.json`` gives it (see ``bench/spec.py``).
"""
