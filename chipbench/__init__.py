"""On-chip benchmark of the RegC runtime (see ``harness.py``)."""
