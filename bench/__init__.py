"""The on-chip benchmark of the served page-node search (see run.py)."""
