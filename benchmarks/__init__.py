"""The on-chip benchmark of lightgbm-tpu (see benchmarks/README.md)."""
