"""Roofline share of the trailing-update kernels: the device time of their
trace events against the trailing update's algorithm-level operations and
bytes (``counts.trailing_flops`` / ``trailing_bytes``, leaf application
plus butterfly combines of every panel over its live window), for the
factorizations of the traced window. The bound is the larger of the two
times at the bf16 peak and the HBM bandwidth."""
from bench import counts

# Pallas kernels of the trailing update, as the device trace names them.
PATTERNS = [r"wy_apply", r"stacked_apply"]


def read(ctx):
    return counts.kernel_roofline(ctx, PATTERNS, "trailing")
