"""The benchmark of the served search path on TPU chips.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything that belongs
to one configuration, traffic mix or metric sits in a file of its own,
found by the name ``BENCHMARK.json`` gives it: ``configs/<config>.json``,
``traffic/<mix>.json``, ``metrics/<metric>.py``, and the plain reference a
configuration names in ``refs/<reference>.py``.
"""
