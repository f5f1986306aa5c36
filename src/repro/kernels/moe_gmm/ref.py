"""Pure-jnp oracle for the grouped expert FFN: every matmul with both
operands rounded to bfloat16 and products accumulated in float32, rows
past the last group 0."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_gmm_ref(x, wg, wi, wo, offsets):
    """x: (M, K); wg, wi: (E, K, F); wo: (E, F, K); offsets: (E + 1,).
    Returns (M, K) float32."""
    sizes = jnp.diff(offsets)

    def mm(a, w):
        return jax.lax.ragged_dot(a.astype(jnp.bfloat16),
                                  w.astype(jnp.bfloat16), sizes,
                                  preferred_element_type=jnp.float32)
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wi), wo)
