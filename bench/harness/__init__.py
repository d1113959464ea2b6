"""Shared machinery of the benchmark: finding cells by name, the device,
statistics, the profiler trace and the result line."""
