"""The on-chip benchmark of the IMM engine (see ``bench/run.py``)."""
