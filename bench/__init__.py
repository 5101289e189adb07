"""The chip benchmark: one cell per run, driven by data.

``python bench/run_cell.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs the cell named in ``BENCHMARK.json``. Everything that
belongs to one configuration, cell, driver or per-layer metric is a file of
its own, found by name:

- ``configs/<config>.json``: the deployment's sizes, source and cuts;
- ``workloads/<cell>.json``: config, driver, traffic and correctness limits;
- ``mixes/<traffic>.json``: the traffic mix's parameters, read by the
  driver;
- ``drivers/<driver>.py``: a ``Cell`` class that sets up, runs the timed
  window and checks what it produced against a plain reference;
- ``metrics/<metric>.py``: a ``read(ctx)`` that returns the per-layer
  number, or ``None`` where the run holds nothing to read.
"""
