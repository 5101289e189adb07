"""Roofline share of the panel-factorization kernels: the device time of
their trace events against the panel work's algorithm-level operations and
bytes (``counts.panel_flops`` / ``panel_bytes``: the leaf QR with its T
over the live rows, and the butterfly's stacked QRs), for the
factorizations of the traced window. The bound is the larger of the two
times at the bf16 peak and the HBM bandwidth."""
from bench import counts

# Pallas kernels of the panel factorization, as the device trace names them.
PATTERNS = [r"panel_qr", r"stacked_qr"]


def read(ctx):
    return counts.kernel_roofline(ctx, PATTERNS, "panel")
