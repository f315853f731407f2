"""TPU v5e per-chip hardware constants for roofline terms:
197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s per ICI link."""

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link
