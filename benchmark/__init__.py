"""The repository's benchmark: one cell per run, found by name (see README.md)."""
