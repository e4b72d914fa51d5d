"""An on-chip benchmark of AsyncSAM training; see PERF.md and BENCHMARK.json."""
