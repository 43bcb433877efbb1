"""On-chip benchmark of the hierarchical compressor (see BENCHMARK.json)."""
