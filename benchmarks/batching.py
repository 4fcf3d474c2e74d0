"""Figure 7 analogue: per-matrix time of the batched Gram-NS execution,
normalized to single-matrix execution, across representative Gram-input
shapes.  Small near-square matrices underfill the device alone and gain the
most from batching; large rectangular ones saturate it and gain little."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import csv_row, time_fn
from repro.core.gram_ns import GramNSConfig, gram_newton_schulz

# (m, n) Gram-input shapes, scaled-down versions of the paper's sweep
SHAPES = [(128, 1408), (256, 1024), (256, 256), (128, 128), (64, 64)]
BATCHES = [1, 2, 4, 8, 16]


def run() -> list[str]:
    rows = []
    cfg = GramNSConfig(num_steps=5)
    fn = jax.jit(lambda x: gram_newton_schulz(x, cfg, assume_short_fat=True))
    for m, n in SHAPES:
        base = None
        for b in BATCHES:
            x = jax.random.normal(jax.random.PRNGKey(0), (b, m, n))
            t = time_fn(fn, x) / b          # per-matrix
            if base is None:
                base = t
            rows.append(csv_row(
                f"fig7/gram_ns/{m}x{n}/batch{b}/per_matrix", t * 1e6,
                derived=f"norm={t/base:.3f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(r)
