"""On-chip benchmark of the consensus sweep engine (see ``run.py``)."""
