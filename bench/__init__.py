"""The chip benchmark of the served DILI index (see `bench/run.py`)."""
