"""The chip benchmark of the Shabari resource manager (see run.py)."""
