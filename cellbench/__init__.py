"""cellbench: the benchmark's cells, the yardstick they are measured by, and
nothing of the program. See README.md."""
