"""Benchmark of the online OEF scheduler on the TPU; see README.md."""
