"""Chip benchmark of the crossbar program stack (see ``run.py``)."""
