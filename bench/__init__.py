"""Chip benchmark of the camera-fleet serving path (``RungBucketScheduler.tick``).

Run ``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
from the repository root on a TPU host.  Cells, configurations, traffic
mixes and metrics are listed in ``BENCHMARK.json`` and found by name under
``bench/configs``, ``bench/traffic``, ``bench/metrics`` and ``bench/limits``;
each configuration names its model module (``bench/models``) and its
plain reference (``bench/references``) by path.
"""
