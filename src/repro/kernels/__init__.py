"""Pallas TPU kernels for the DMuon Gram Newton-Schulz execution stack.

Modules:
  symmul     — batched symmetric-output matmul, lower-triangle compute,
               fused polynomial epilogue (the paper's "symmetric Gram kernel")
  gram_syrk  — batched G = X Xᵀ, lower-triangle compute
  ops        — public jit'd wrappers (mirror epilogue, autotune dispatch)
  ref        — pure-jnp oracles used by tests and by the CPU/dry-run path
  autotune   — block-shape search + persistent cache (paper Fig. 6)
"""

import jax


def interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode: on the CPU backend
    only (tests and rehearsals), compiled everywhere else.  The one place the
    choice is made; the public ops pass it down to the raw kernels."""
    return jax.default_backend() == "cpu"


from repro.kernels import autotune, ops, ref  # noqa: E402,F401
