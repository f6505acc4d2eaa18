"""Reduction of a profiler trace to the numbers the metrics read."""
