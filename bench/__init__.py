"""The chip benchmark: open-loop serving cells, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on.  Each
configuration, traffic mix, cell and metric is a file of its own here
(``configs/``, ``traffic/``, ``workloads/``, ``metrics/``), found by the
name ``BENCHMARK.json`` gives it.
"""
