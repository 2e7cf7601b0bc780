"""Chip benchmark for the serving engine and the trainer.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU that the process finds.
Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by its name:

- ``bench/configs/<config>.json``: the published config as it is run;
- ``bench/models/<family>_ref.py``: the plain float32 reference, the
  weights made from the seed, and the operation and byte counts;
- ``bench/models/<family>_program.py``: how the program under test is
  built from the config and handed those weights;
- ``bench/traffic/<mix>.json``: parameters for the one traffic generator;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric.
"""
