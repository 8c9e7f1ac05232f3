"""Chip benchmark of the serving main path; see run.py."""
