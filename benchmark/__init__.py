"""On-chip benchmark of the MoEBlaze train step (see ``run.py``)."""
