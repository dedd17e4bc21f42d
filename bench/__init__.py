"""On-chip benchmark of the norm-ranging LSH index (see BENCHMARK.json)."""
