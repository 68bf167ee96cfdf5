"""Pure-jnp oracle for the SSD intra-chunk computation.

Per (batch·chunk, head): given xdt (Q,P), B (Q,N), C (Q,N) and the
inclusive cumulative decay csum (Q,):
    y_intra[q] = Σ_{t<=q} exp(csum_q - csum_t) · (C_q·B_t) · xdt_t
    state      = Σ_t exp(csum_Q - csum_t) · B_t ⊗ xdt_t      (N, P)
which is the attention-form dual of the selective-scan recurrence
(arXiv:2405.21060 §5) restricted to one chunk.
"""
from __future__ import annotations

import jax.numpy as jnp


def ssd_chunk_ref(xdt, b, c, csum):
    """xdt (..., Q, P); b/c (..., Q, N); csum (..., Q).

    Returns (y_intra (..., Q, P), state (..., N, P))."""
    cb = jnp.einsum("...qn,...tn->...qt", c, b,
                    preferred_element_type=jnp.float32)
    diff = csum[..., :, None] - csum[..., None, :]          # (..., Q, Q)
    Q = xdt.shape[-2]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(mask, diff, -jnp.inf))  # no inf·0 in grads
    y = jnp.einsum("...qt,...tp->...qp", (cb * decay).astype(xdt.dtype), xdt)
    to_end = jnp.exp(csum[..., -1:] - csum)                 # (..., Q)
    state = jnp.einsum(
        "...tn,...tp->...np",
        (b * to_end[..., None]).astype(jnp.float32),
        xdt.astype(jnp.float32),
    )
    return y, state
