"""On-chip benchmark of the MHD fleet trainer (see run.py)."""
