"""On-chip serving benchmark: one cell (a model configuration under a
traffic mix) per run of ``bench/run.py``. See PERF.md for the cells, the
metrics and their bounds."""
