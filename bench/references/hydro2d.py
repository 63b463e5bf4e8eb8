"""Plain reference of one Hydro2D step (the CEA/PRACE Hydro mini-app,
HFAV paper arXiv:1710.08774 section 5.4), written from its equations and
sharing no code with the program under test.

The state is ``(rho, mu, mv, en)``: density, x and y momentum, total
energy.  A step is an x pass over the whole array, then a y pass over
what the x pass made, as two HydroC passes with a halo refill between
them would run.  One pass along a line of cells, with ``n`` the normal
and ``t`` the transverse momentum:

1. primitives ``r = max(rho, smallr)``, ``un = n / r``, ``ut = t / r``,
   ``p = max((gamma - 1)(en - r (un^2 + ut^2) / 2), r smallp)``;
2. minmod slopes ``dq`` of ``r, un, ut, p``;
3. the Hancock half step in primitive form, then the state left of face
   ``k + 1/2`` (``q + dq / 2``) and right of face ``k - 1/2``
   (``q - dq / 2``), density and pressure floored as in 1;
4. HydroC's iterative Riemann solver at each face (10 iterations), the
   Godunov state and its flux ``(r u, r u^2 + p, r u v,
   u (p / (gamma - 1) + r (u^2 + v^2) / 2 + p))``;
5. ``U - dtdx (F[k + 1/2] - F[k - 1/2])`` on cells ``[2, N - 2)``.

Every line of a pass is independent of the others, so each pass runs
over blocks of lines, which keeps the temporaries small beside a run's
buffers.  The step's cells are ``[2, Nj - 2) x [2, Ni - 2)``; the
two-cell border of each output is zero.
"""
import jax
import jax.numpy as jnp

GAMMA = 1.4
SMALLR = 1e-10
SMALLC = 1e-10
SMALLP = SMALLC ** 2 / GAMMA
DTDX = 0.1
NITER = 10
#: Lines per block of a pass.
BLOCK = 512


def reference(inputs: dict, dtype) -> dict:
    """``{"rho_new", "mu_new", "mv_new", "en_new"}`` of ``inputs``,
    computed in ``dtype``."""
    dtype = jnp.dtype(dtype)
    rho, mu, mv, en = (inputs[k].astype(dtype) for k in ("rho", "mu", "mv", "en"))
    # x pass along rows: normal momentum mu; cells [2, Ni - 2) of every row
    xr, xu, xv, xe = _blocks(rho, mu, mv, en)
    # y pass along columns: normal momentum mv
    yr, yv, yu, ye = _blocks(xr.T, xv.T, xu.T, xe.T)
    out = {"rho_new": yr.T, "mu_new": yu.T, "mv_new": yv.T, "en_new": ye.T}
    return {k: jnp.pad(v.astype(jnp.float32), 2) for k, v in out.items()}


def _blocks(rho, mn, mt, en):
    """:func:`_line_pass` over blocks of :data:`BLOCK` lines (rows)."""
    parts = [_line_pass(rho[s:s + BLOCK], mn[s:s + BLOCK], mt[s:s + BLOCK],
                        en[s:s + BLOCK]) for s in range(0, rho.shape[0], BLOCK)]
    return tuple(jnp.concatenate(q, axis=0) for q in zip(*parts))


def _primitive(rho, mn, mt, en):
    r = jnp.maximum(rho, SMALLR)
    un = mn / r
    ut = mt / r
    eint = en - 0.5 * r * (un ** 2 + ut ** 2)
    p = jnp.maximum((GAMMA - 1.0) * eint, r * SMALLP)
    return r, un, ut, p


def _slope(q):
    """Minmod slope of ``q`` on cells ``[1, N - 1)`` of each line."""
    left = q[:, 1:-1] - q[:, :-2]
    right = q[:, 2:] - q[:, 1:-1]
    smaller = jnp.where(jnp.abs(left) < jnp.abs(right), jnp.abs(left), jnp.abs(right))
    return jnp.where(left * right > 0.0, jnp.sign(left) * smaller, 0.0)


def _riemann_flux(rl, ul, vl, pl, rr, ur, vr, pr):
    """Flux through faces with left state ``l`` and right state ``r``."""
    g6 = (GAMMA + 1.0) / (2.0 * GAMMA)
    cl = GAMMA * pl * rl
    cr = GAMMA * pr * rr
    wl = jnp.sqrt(cl)
    wr = jnp.sqrt(cr)
    pstar = (wr * pl + wl * pr + wl * wr * (ul - ur)) / (wl + wr)
    pstar = jnp.maximum(pstar, 0.0)

    def w_at(c, pk, ps):
        return jnp.sqrt(c * (1.0 + g6 * (ps - pk) / pk))

    for _ in range(NITER):
        wwl = w_at(cl, pl, pstar)
        wwr = w_at(cr, pr, pstar)
        ql = 2.0 * wwl ** 3 / (wwl ** 2 + cl)
        qr = 2.0 * wwr ** 3 / (wwr ** 2 + cr)
        usl = ul - (pstar - pl) / wwl
        usr = ur + (pstar - pr) / wwr
        delp = jnp.maximum(qr * ql / (qr + ql) * (usl - usr), -pstar)
        pstar = pstar + delp
    wl = w_at(cl, pl, pstar)
    wr = w_at(cr, pr, pstar)
    ustar = 0.5 * (ul + (pl - pstar) / wl + ur - (pr - pstar) / wr)

    from_left = ustar > 0.0
    sgn = jnp.where(from_left, 1.0, -1.0)

    def pick(a, b):
        return jnp.where(from_left, a, b)

    ro, uo, po, wo, vo = pick(rl, rr), pick(ul, ur), pick(pl, pr), pick(wl, wr), pick(vl, vr)
    rstar = jnp.maximum(ro / (1.0 + ro * (po - pstar) / wo ** 2), SMALLR)
    co = jnp.maximum(jnp.sqrt(GAMMA * po / ro), SMALLC)
    cstar = jnp.maximum(jnp.sqrt(GAMMA * pstar / rstar), SMALLC)
    spout = co - sgn * uo
    spin = cstar - sgn * ustar
    ushock = wo / ro - sgn * uo
    spout = jnp.where(pstar >= po, ushock, spout)
    spin = jnp.where(pstar >= po, ushock, spin)
    scr = jnp.maximum(spout - spin, SMALLC + jnp.abs(spout + spin))
    frac = jnp.clip(0.5 * (1.0 + (spout + spin) / scr), 0.0, 1.0)

    def godunov(star, o):
        mixed = frac * star + (1.0 - frac) * o
        return jnp.where(spin > 0.0, star, jnp.where(spout < 0.0, o, mixed))

    rg, ug, pg = godunov(rstar, ro), godunov(ustar, uo), godunov(pstar, po)
    etot = pg / (GAMMA - 1.0) + 0.5 * rg * (ug ** 2 + vo ** 2)
    return rg * ug, rg * ug ** 2 + pg, rg * ug * vo, ug * (etot + pg)


@jax.jit
def _line_pass(rho, mn, mt, en):
    """One pass along the last axis; returns the four fields updated on
    cells ``[2, N - 2)`` of each line."""
    r, un, ut, p = _primitive(rho, mn, mt, en)
    dr, dun, dut, dp = (_slope(q) for q in (r, un, ut, p))
    r, un, ut, p = (q[:, 1:-1] for q in (r, un, ut, p))   # cells [1, N - 1)
    half = 0.5 * DTDX
    rh = r - half * (un * dr + r * dun)
    unh = un - half * (un * dun + dp / r)
    uth = ut - half * (un * dut)
    ph = p - half * (GAMMA * p * dun + un * dp)

    def floored(rq, pq):
        rq = jnp.maximum(rq, SMALLR)
        return rq, jnp.maximum(pq, rq * SMALLP)

    rplus, pplus = floored(rh + 0.5 * dr, ph + 0.5 * dp)
    rminus, pminus = floored(rh - 0.5 * dr, ph - 0.5 * dp)
    uplus, vplus = unh + 0.5 * dun, uth + 0.5 * dut
    uminus, vminus = unh - 0.5 * dun, uth - 0.5 * dut
    # faces k + 1/2 for cells k in [1, N - 2): left is cell k's plus
    # state, right cell k + 1's minus state
    flux = _riemann_flux(rplus[:, :-1], uplus[:, :-1], vplus[:, :-1], pplus[:, :-1],
                         rminus[:, 1:], uminus[:, 1:], vminus[:, 1:], pminus[:, 1:])
    # cells [2, N - 2): faces k + 1/2 are flux index 1.., k - 1/2 index 0..
    return tuple(u[:, 2:-2] - DTDX * (f[:, 1:] - f[:, :-1])
                 for u, f in zip((rho, mn, mt, en), flux))
