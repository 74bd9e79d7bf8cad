"""The chip benchmark of the treecode: `python bench/run.py --workload <cell> ...`."""
