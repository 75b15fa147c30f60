"""The on-chip benchmark of the k-core engine and server (see BENCHMARK.json)."""
