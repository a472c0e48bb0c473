"""Reduction of a JAX profiler trace to device busy time, op and kernel times."""
