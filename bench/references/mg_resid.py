"""Plain reference of NPB MG's finest-level residual and its norms,
written from ``resid`` and ``norm2u3`` of ``mg.f`` (NPB 3.x) and sharing
no code with the program under test.

``r = v - A u`` on the interior ``[1, N-1)`` of every dim, zero on the
one-cell border, with the 27-point ``A`` of ``mg.f``: ``a(0) = -8/3`` on
the centre, ``a(1) = 0`` on the 6 faces (left out, as ``mg.f`` leaves
it out), ``a(2) = 1/6`` on the 12 edges and ``a(3) = 1/12`` on the 8
corners.  The edge and corner sums are grouped as ``mg.f`` groups them,
through its lane-wide partial sums ``u1`` (the four ``j``/``k`` face
neighbours) and ``u2`` (the four ``j``/``k`` diagonal neighbours).  Then
``rnm2 = sqrt(sum r^2 / (nx ny nz))`` and ``rnmu = max |r|`` over the
interior, where ``nx ny nz`` is class C's grid, 512^3, by which
``norm2u3`` divides whatever block of it an array holds.
"""
import jax
import jax.numpy as jnp

#: a(0), a(2), a(3) of mg.f; a(1) is 0.
A0, A2, A3 = -8.0 / 3.0, 1.0 / 6.0, 1.0 / 12.0
#: nx * ny * nz of NPB MG class C.
CLASS_C_POINTS = 512 * 512 * 512


def reference(inputs: dict, dtype) -> dict:
    """``{"r", "rnm2", "rnmu"}`` of ``inputs["u"]`` and ``inputs["v"]``,
    computed in ``dtype`` and returned in float32."""
    return _resid(inputs["u"], inputs["v"], jnp.dtype(dtype))


@jax.jit(static_argnums=2)
def _resid(u, v, dtype):
    u = u.astype(dtype)
    v = v.astype(dtype)

    def at(dk, dj):  # u at plane k + dk and row j + dj, every lane
        nk, nj, _ = u.shape
        return u[1 + dk:nk - 1 + dk, 1 + dj:nj - 1 + dj, :]

    u1 = at(0, -1) + at(0, 1) + at(-1, 0) + at(1, 0)
    u2 = at(-1, -1) + at(-1, 1) + at(1, -1) + at(1, 1)
    r = (v[1:-1, 1:-1, 1:-1] - A0 * at(0, 0)[..., 1:-1]
         - A2 * (u2[..., 1:-1] + u1[..., :-2] + u1[..., 2:])
         - A3 * (u2[..., :-2] + u2[..., 2:]))
    rnm2 = jnp.sqrt(jnp.sum(r * r) / CLASS_C_POINTS)
    rnmu = jnp.max(jnp.abs(r))
    f32 = jnp.float32
    return {"r": jnp.pad(r, 1).astype(f32), "rnm2": rnm2.astype(f32),
            "rnmu": rnmu.astype(f32)}
