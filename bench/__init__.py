"""The on-chip benchmark of the pallas serving path (``BENCHMARK.json``).

Run one cell with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout, on a machine
that holds a TPU.  Everything that belongs to one configuration, traffic
mix or per-layer metric is a file of its own under ``configs/``,
``traffic/`` and ``metrics/``, found by the name ``BENCHMARK.json`` gives
it (:mod:`bench.layout`).
"""
