"""Chip benchmark of NeurStore: see bench/README.md."""
