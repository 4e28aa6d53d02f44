"""Chip benchmark of the multicut solve (see ``bench/run.py``)."""
