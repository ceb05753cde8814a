"""Chip benchmark of the BBMM GP library: cells, traffic, metrics and the
plain reference that decides ``correct``.  Entry point: ``bench/run.py``."""
