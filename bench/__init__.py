"""On-chip training benchmark: harness, data, weights, reference and trace
reduction.  Entry point: ``python bench/run.py --workload <name> ...``."""


class CellFailed(Exception):
    """The run cannot give a result; nothing is printed on stdout."""
