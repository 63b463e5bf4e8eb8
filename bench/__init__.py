"""The chip benchmark of the HFAV stencil compiler.

One command runs one cell of ``BENCHMARK.json`` once::

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own and is found by name (:mod:`bench.spec`):
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``,
``bench/metrics/<metric>.py`` and ``bench/references/<program>.py``.
"""
