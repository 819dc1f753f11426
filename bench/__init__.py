"""The serving benchmark: one command, cells as data (see run.py)."""
