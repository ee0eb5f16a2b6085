"""Reductions from raw profiler output to numbers."""
