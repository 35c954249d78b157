"""Chip benchmark of the skewed-join serving engine: `python bench/run.py`."""
