"""The chip benchmark: one command, cells defined by data.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
serves one cell of ``BENCHMARK.json`` on the accelerator it finds and
prints one JSON line.  A cell names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); each metric, end-to-end or per-layer,
is a reader of its own (``bench/metrics/<name>.py``).  All three are
found by name, so a new cell, mix or metric is a new file and an entry
in ``BENCHMARK.json``.
"""
