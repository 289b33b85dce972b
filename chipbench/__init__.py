"""Chip benchmark of the 8-bit optimizer training step and the paged
server (see ``run.py``)."""
