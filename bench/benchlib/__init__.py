"""The benchmark's own code: harness, cells, trace reductions, work counters."""
