"""The chip benchmark's harness: everything that is the same for every cell.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix (``bench/mixes/<name>.json``);
its offered rate and its correctness limit sit in ``bench/cells/<cell>.json``,
and each metric is computed by ``bench/metrics/<metric>.py``.  The harness finds
all of them by name, so a new cell, configuration, mix or metric is a new file.
"""
