"""On-chip benchmark of the Ember system: one cell of ``BENCHMARK.json``
per run, driven by data files found by name (see ``chipbench/run.py``)."""
