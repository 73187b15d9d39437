"""Benchmark of PredTrace's lineage serving on a TPU (see BENCHMARK.json)."""
