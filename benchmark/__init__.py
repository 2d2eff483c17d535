"""The benchmark harness: BENCHMARK.json names what is here."""
