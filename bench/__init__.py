"""Chip benchmark of MCAL labeling campaigns (``python3 bench/run.py``)."""
