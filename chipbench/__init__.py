"""Chip benchmark of the cache simulator: one cell per run, driven by data.

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``:
the deployment's catalogue, skew and tier tree) and a traffic mix
(``traffic/<mix>.json``: the driver, the scenario and its parameters). The
harness finds everything else by name too: the driver in
``drivers/<driver>.py``, the scenario generator in ``scenarios/<name>.py``,
the plain reference policy of each kind in ``reference/<kind>.py`` and each
metric's reader in ``metrics/<metric>.py``. A new cell, configuration, mix or
metric is therefore new files plus entries in ``BENCHMARK.json``.

Run one cell (on a machine with a TPU):

    python3 chipbench/run.py --workload plfua_n100k.stream --seed 7 \
        --seconds 20 --trace 0
"""
