"""The on-chip benchmark of the served Lasso path (see BENCHMARK.json)."""
