"""The benchmark of this repository: BENCHMARK.json at the root names what is here."""
