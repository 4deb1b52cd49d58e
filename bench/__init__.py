"""On-chip benchmark of the posit serving path (see ``bench/run.py``)."""
