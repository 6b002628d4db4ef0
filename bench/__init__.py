"""On-chip benchmark of the Harvest serving stack (see ``bench/run.py``)."""
