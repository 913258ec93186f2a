"""Chip benchmark of the federated FP8 round (see ``bench/run.py``)."""
