"""Paper §5.3 / Fig. 11: COSMO fourth-order diffusion micro-kernels.

Four legs: unfused (4 sweeps, 3 materialized intermediates), HFAV-fused
JAX backend (single sweep, rolling buffers), a 'STELLA-like' leg that
fuses only the final three kernels with redundant flux recompute — the
paper's comparison point — and the Pallas stencil-executor leg
(``backend="pallas"``, VMEM rolling windows over a (k, j) grid).  Footprint note: our lead analysis needs
only 4 buffer rows (ulap 2 + fy 2, fx row-local) vs the paper's 5
(EXPERIMENTS.md §Benchmarks)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compile_program
from repro.core.interpreters import resolve_interpret
from repro.core.programs import cosmo_program, _ulap, _flux_x, _flux_y, _ustage
from repro.core.unfused import build_unfused

from .common import mk, pallas_leg_row, time_fn


def stella_like(u):
    """Fuse flux_x/flux_y/ustage with redundant flux recompute; laplacian
    materialized (the optimized STELLA variant described in §5.3)."""
    lap = jnp.zeros_like(u)
    lap = lap.at[:, 1:-1, 1:-1].set(
        _ulap(u[:, :-2, 1:-1], u[:, 1:-1, 2:], u[:, 2:, 1:-1],
              u[:, 1:-1, :-2], u[:, 1:-1, 1:-1])
    )
    fx = jnp.zeros_like(u)
    fx = fx.at[:, :, :-1].set(_flux_x(u[:, :, :-1], u[:, :, 1:],
                                      lap[:, :, :-1], lap[:, :, 1:]))
    fy = jnp.zeros_like(u)
    fy = fy.at[:, :-1, :].set(_flux_y(u[:, :-1, :], u[:, 1:, :],
                                      lap[:, :-1, :], lap[:, 1:, :]))
    out = jnp.zeros_like(u)
    out = out.at[:, 2:-2, 2:-2].set(
        _ustage(u[:, 2:-2, 2:-2], fx[:, 2:-2, 1:-3], fx[:, 2:-2, 2:-2],
                fy[:, 1:-3, 2:-2], fy[:, 2:-2, 2:-2])
    )
    return out


PALLAS_MAX_ROWS = 96  # interpret mode unrolls the grid at trace time


def run(sizes=((8, 64, 64), (16, 128, 128), (8, 256, 512)), interpret=None):
    interpret = resolve_interpret(interpret)
    prog = cosmo_program()
    gen = compile_program(prog, backend="jax")
    unfused = build_unfused(prog, per_pass_jit=True).fn      # leg A: autovec
    fusedvec_fn = jax.jit(lambda u: build_unfused(prog).fn(u=u)["unew"])  # leg B
    rolling_fn = jax.jit(lambda u: gen.fn(u)["unew"])         # leg C
    stella_fn = jax.jit(stella_like)
    pallas_gen = compile_program(prog, backend="pallas", interpret=interpret)
    pallas_fn = jax.jit(lambda u: pallas_gen.fn(u=u)["unew"])  # leg D
    rng = np.random.default_rng(1)
    rows = []
    for shp in sizes:
        u = mk(rng, shp)
        t_a, a = time_fn(lambda u: unfused(u=u)["unew"], u)
        t_s, s_ = time_fn(stella_fn, u)
        t_b, b = time_fn(fusedvec_fn, u)
        t_c, c = time_fn(rolling_fn, u)
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)
        assert np.allclose(np.asarray(a), np.asarray(c), atol=1e-4)
        assert np.allclose(np.asarray(a), np.asarray(s_), atol=1e-4)
        cells = shp[0] * shp[1] * shp[2]
        t_best = min(t_b, t_c)
        rows.append({
            "name": f"cosmo_{shp[0]}x{shp[1]}x{shp[2]}",
            "us_per_call": t_best * 1e6,
            "derived": (
                f"unfused_us={t_a*1e6:.0f};stella_us={t_s*1e6:.0f};"
                f"fusedvec_us={t_b*1e6:.0f};rolling_us={t_c*1e6:.0f};"
                f"speedup_vs_unfused={t_a/t_best:.2f}x;"
                f"speedup_vs_stella={t_s/t_best:.2f}x;"
                f"buffers=4rows_vs_paper5;Mcells_s={cells/t_best/1e6:.0f}"
            ),
        })
    # Pallas leg (single streamed (k, j) grid; bounded size off-TPU —
    # interpret mode unrolls the grid at trace time)
    nk, nj, ni = min(sizes)
    if interpret:
        nk, nj = min(nk, 4), min(nj, PALLAS_MAX_ROWS)
    u = mk(rng, (nk, nj, ni))
    ref = build_unfused(prog).fn(u=u)["unew"]
    rows.append(pallas_leg_row(
        f"cosmo_pallas_{nk}x{nj}x{ni}", pallas_fn, ref, u,
        interpret=interpret, atol=1e-4))
    return rows
