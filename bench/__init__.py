"""On-chip benchmark of the DetectorPool serving path (see ``run.py``)."""
