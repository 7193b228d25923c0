"""The benchmark harness: one cell per process (see ``bench/run.py``)."""
