"""The benchmark's harness: cell loading, the trial function, the windowed
fold, the trace reduction and the checks. Nothing here names a cell, a model
family or a metric: those are files found by the names in BENCHMARK.json."""
