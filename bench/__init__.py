"""The chip benchmark of the served market (``python3 bench/run_cell.py``)."""
