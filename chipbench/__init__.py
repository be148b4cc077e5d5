"""On-chip benchmark of the JAX serving path: see ``run.py``."""
