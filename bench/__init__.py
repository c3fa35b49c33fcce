"""On-chip benchmark of ColA: one command runs one cell (a configuration
under a traffic mix) and prints one JSON line. See ``run.py``."""
