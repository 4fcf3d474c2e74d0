"""Production mesh definition (deliverable e).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""

from __future__ import annotations

from repro.runtime.elastic import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2×16×16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
HBM_BYTES = 16 * 2**30         # per-chip HBM capacity
