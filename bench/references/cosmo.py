"""Plain reference of COSMO's fourth-order horizontal diffusion (the
paper's ``ulapstage -> flux_x / flux_y -> ustage`` chain, arXiv:1710.08774
section 5.3), written from its equations and sharing no code with the
program under test.  Every level ``k`` is independent::

    lap[j, i] = u[j-1, i] + u[j, i+1] + u[j+1, i] + u[j, i-1] - 4 u[j, i]
    fx[j, i]  = limit(lap[j, i+1] - lap[j, i], u[j, i+1] - u[j, i])
    fy[j, i]  = limit(lap[j+1, i] - lap[j, i], u[j+1, i] - u[j, i])
    unew      = u - 0.1 ((fx[j, i] - fx[j, i-1]) + (fy[j, i] - fy[j-1, i]))

with ``limit(f, d) = 0 where f * d > 0 else f``, on ``j in [2, Nj-2)``,
``i in [2, Ni-2)`` and zero on the two-cell border.
"""
import jax
import jax.numpy as jnp


def reference(inputs: dict, dtype) -> dict:
    """``{"unew": array}`` of ``inputs["u"]``, computed in ``dtype``."""
    return {"unew": _unew(inputs["u"], jnp.dtype(dtype))}


def _limit(f, d):
    return jnp.where(f * d > 0.0, 0.0, f).astype(f.dtype)


@jax.jit(static_argnums=1)
def _unew(u, dtype):
    u = u.astype(dtype)
    # lap on [1, N-1) in j and i: index 0 is grid point 1
    lap = (u[:, :-2, 1:-1] + u[:, 1:-1, 2:] + u[:, 2:, 1:-1] + u[:, 1:-1, :-2]
           - 4.0 * u[:, 1:-1, 1:-1])
    uc = u[:, 1:-1, 1:-1]
    # fluxes on [1, N-2) along their own dim, index 0 is grid point 1
    fx = _limit(lap[:, :, 1:] - lap[:, :, :-1], uc[:, :, 1:] - uc[:, :, :-1])
    fy = _limit(lap[:, 1:, :] - lap[:, :-1, :], uc[:, 1:, :] - uc[:, :-1, :])
    # unew on [2, N-2): fx at i and i-1, fy at j and j-1
    fx_i, fx_im = fx[:, 1:-1, 1:], fx[:, 1:-1, :-1]
    fy_j, fy_jm = fy[:, 1:, 1:-1], fy[:, :-1, 1:-1]
    out = u[:, 2:-2, 2:-2] - 0.1 * ((fx_i - fx_im) + (fy_j - fy_jm))
    return jnp.pad(out, ((0, 0), (2, 2), (2, 2))).astype(jnp.float32)
