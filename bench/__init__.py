"""On-chip benchmark of the round engine (see ``run_cell.py``)."""
