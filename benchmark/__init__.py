"""The on-chip benchmark: harness, yardstick and data files (see PERF.md)."""
