"""On-chip benchmark of the served query path (see ``run.py``)."""
