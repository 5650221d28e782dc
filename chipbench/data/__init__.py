"""Data generators shared by the benchmark's configurations."""
