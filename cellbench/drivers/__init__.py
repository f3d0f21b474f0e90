"""Found by name from the benchmark's data files; see ../README.md."""
