"""Plain reference of the 7-point 3-D heat stencil, written from its
equation and sharing no code with the program under test.

``heat[k, j, i] = c + 0.1 * (u[k-1] + u[k+1] + u[j-1] + u[j+1] + u[i-1]
+ u[i+1] - 6 c)`` on the interior ``[1, N-1)`` of every dim, zero on the
one-cell border.  The terms are summed in the order the equation lists
them.
"""
import jax
import jax.numpy as jnp


def reference(inputs: dict, dtype) -> dict:
    """``{"heat": array}`` of ``inputs["u"]``, computed in ``dtype``."""
    return {"heat": _heat(inputs["u"], jnp.dtype(dtype))}


@jax.jit(static_argnums=1)
def _heat(u, dtype):
    u = u.astype(dtype)
    c = u[1:-1, 1:-1, 1:-1]
    out = c + 0.1 * (u[:-2, 1:-1, 1:-1] + u[2:, 1:-1, 1:-1]
                     + u[1:-1, :-2, 1:-1] + u[1:-1, 2:, 1:-1]
                     + u[1:-1, 1:-1, :-2] + u[1:-1, 1:-1, 2:] - 6.0 * c)
    return jnp.pad(out, 1).astype(jnp.float32)
