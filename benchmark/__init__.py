"""The repo's on-chip benchmark: harness, references and data (see PERF.md)."""
