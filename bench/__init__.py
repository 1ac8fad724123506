"""On-chip benchmark of the continuous speculative server (see PERF.md)."""
