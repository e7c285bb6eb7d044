"""Chip benchmark of the federated sub-model round.

    python -m bench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` once on the TPU and prints one JSON
line.  Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own that the harness finds by the
name in ``BENCHMARK.json``:

- ``bench/configs/<config>.json``   model sizes as run, with the source
  and the name of its plain reference, ``bench/reference/<name>.py``;
- ``bench/traffic/<traffic>.json``  the federated job (clients, K, batch,
  sequence length, window scheme) and the ``"generator"`` that feeds it,
  ``bench/traffic/<generator>.py`` (its ``Feed`` class);
- ``bench/limits/<cell>.json``      the limits of the correctness check;
- ``bench/metrics/<metric>.py``     the reader of one per-layer metric.

The program under test is imported from ``<checkout>/src``.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
