"""TPU v5e hardware constants for the roofline model."""

HBM_BW = 819e9                  # bytes/s per chip
