"""Pure-jnp oracle for the weight-streaming matmul: both operands rounded
to bfloat16, products accumulated in float32."""
from __future__ import annotations

import jax.numpy as jnp


def wstream_matmul_ref(x, w):
    """x: (M, K); w: (K, N). Returns (M, N) float32."""
    return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
