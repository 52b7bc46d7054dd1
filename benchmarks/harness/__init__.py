"""The benchmark's yardstick: everything `run.py` needs besides the data
files.  Nothing here names a cell, a model or a metric."""
