"""On-chip benchmark of FastMoE training: ``python3 bench/run.py --help``."""
