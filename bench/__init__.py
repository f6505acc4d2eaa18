"""The chip benchmark: one command, cells defined by data.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
serves one cell of ``BENCHMARK.json`` on the accelerator it finds and
prints one JSON line.  A cell names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); each metric, end-to-end or per-layer,
is a reader of its own (``bench/metrics/<name>.py``); a configuration
names its model family's module (``bench/reference/<module>.py``: the
reference, the source keys, the FLOP and byte counts, the weight draws).
All are found by name, so a new cell, mix, metric or family is a new
file and an entry in ``BENCHMARK.json``.
"""
