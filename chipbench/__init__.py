"""Chip benchmark of the simulator's Study path (see BENCHMARK.json)."""
