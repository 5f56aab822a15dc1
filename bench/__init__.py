"""Chip benchmark of the multi-task AoT serve path (see BENCHMARK.json)."""
