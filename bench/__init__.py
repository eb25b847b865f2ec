"""The on-chip benchmark of the KQ-SVD serving engine (see run.py)."""
