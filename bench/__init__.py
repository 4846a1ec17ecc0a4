"""On-chip training benchmark: harness, data, weights, reference and trace
reduction.  Entry point: ``python bench/run.py --workload <name> ...``."""
