"""The benchmark's own code: drivers, schedule maker, weights, plain
reference, operation counts, peak table and trace reduction. Nothing here
belongs to one cell; cells are data (see benchmarks/README.md)."""
