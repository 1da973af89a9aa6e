"""Chip benchmark of the streaming engine and its LSM store.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the TPU it is started on.
Everything that belongs to one configuration, traffic mix, driver kind,
reference or per-layer metric lives in a file of its own, found by name:

* ``configs/<config>.json``     a deployment (named by ``BENCHMARK.json``)
* ``traffic/<traffic>.json``    a load pattern, naming its driver kind
* ``drivers/<kind>.py``         how the window drives the engine
* ``references/<semantics>.py`` the plain reference of an operator
* ``metrics/<metric>.py``       the reader of one per-layer metric
"""
