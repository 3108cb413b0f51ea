"""Operation and byte counts of the program's kernels, one module per kernel."""
