"""Chip benchmark of the H-SADMM training round (see ``run.py``)."""
