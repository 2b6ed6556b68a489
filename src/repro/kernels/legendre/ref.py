"""Pure-jnp oracle for the Legendre contraction kernel."""

import jax
import jax.numpy as jnp


def legendre_contract_ref(x: jax.Array, table: jax.Array) -> jax.Array:
    """out[b, n, m] = sum_k x[b, k, m] * table[m, k, n]."""
    return jnp.einsum("bkm,mkn->bnm", x.astype(jnp.float32),
                      table.astype(jnp.float32))
