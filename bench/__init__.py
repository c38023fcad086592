"""The chip benchmark: see run.py."""
