"""On-chip benchmark of the FediAC system (``python bench/run.py``)."""
