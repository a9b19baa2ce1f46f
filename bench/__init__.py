"""Chip benchmark of the DSCS function layer: one command runs one cell.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``BENCHMARK.json`` at the
checkout's root names the cell, its configuration file, its traffic file
(``bench/traffic/<name>.json``) and its metrics (``bench/metrics/<name>.py``);
the configuration names its model (``bench/models/<model>.py``:
``init(key, cfg)``, the weights from the seed; ``forward(p, frames, cfg,
passes=None)``, the plain reference; ``convs(cfg)`` and
``head_flops(cfg)``, the operations of one request; ``CPU_SIZE``, the
configuration keys at which the CPU serves a request in seconds, for the
tests) and its runner
(``bench/runners/<runner>.py``: the system under test); the traffic file
names its client (``bench/clients/<client>.py``).
"""
