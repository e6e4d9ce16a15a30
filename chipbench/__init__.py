"""On-chip benchmark of the private retrieval service (see PERF.md)."""
