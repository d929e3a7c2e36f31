"""On-chip benchmark of the PIM arithmetic path (see PERF.md)."""
