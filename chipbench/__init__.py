"""The chip benchmark: one harness, data files per configuration,
traffic mix and cell limits, and one reader per per-layer metric."""
