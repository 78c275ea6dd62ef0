"""On-chip benchmark of the SWAN serve engine (see ``bench/run.py``)."""
