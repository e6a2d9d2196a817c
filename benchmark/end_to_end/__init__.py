"""The repo's benchmark: see benchmark/README.md and BENCHMARK.json."""
