"""Chip benchmark of the served QAC path; see ``bench/run_cell.py``."""
