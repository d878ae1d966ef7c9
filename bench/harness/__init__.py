"""Chip benchmark harness: one cell per run, found by name (see bench/run.py)."""
