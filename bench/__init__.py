"""On-chip benchmark of the MP-RW-LSH serving path (see BENCHMARK.json)."""
