"""The chip benchmark of the compressed bitmap index (see bench/run.py)."""
