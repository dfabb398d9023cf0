"""On-chip serving benchmark: one cell (configuration x traffic mix) per run.

    python -m bench.run --workload <config>.<mix> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``configs/<config>.json``,
``cells/<cell>.json`` (load and deployment), ``traffic/<mix>.json`` with
its generator ``traffic/<kind>.py``, and one reader per per-layer metric
in ``metrics/<metric>.py``.  See PERF.md at the repository root.
"""
