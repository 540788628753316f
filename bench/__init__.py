"""The benchmark: one harness driven by the data files beside it (see run.py)."""
