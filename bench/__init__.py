"""Chip benchmark of the XDMA movement plane: ``python bench/run.py``."""
