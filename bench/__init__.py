"""On-chip benchmark of the Sponge serving system (see ``bench/run.py``)."""
