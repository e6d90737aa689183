"""Chip benchmark of the served planner path (see ``bench/run.py``)."""
