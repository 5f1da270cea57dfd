"""Chip benchmark of the served path (``python3 bench/run.py --help``)."""
