"""Paper §5.2 / Fig. 12: the normalization example.

Compares the unfused baseline ('autovec': one pass per kernel, five
sweeps of the (j,i) space, all intermediates materialized) against the
HFAV-fused output (two loop nests — the reduction->broadcast split —
with the flux intermediate as the only materialized array).  The paper's
claim: fusion cuts the sweeps from five to two and wins for problems
that fall out of cache.

A fourth leg drives the same split schedule through the Pallas stencil
executor (``backend="pallas"``: two stencil calls with a carried
accumulator).  Off-TPU it runs in interpret mode — grid steps unroll at
trace time — so it is timed on a bounded size; on a TPU it compiles for
the chip."""
from __future__ import annotations

import jax
import numpy as np

from repro.core import compile_program
from repro.core.interpreters import resolve_interpret
from repro.core.programs import normalization_program
from repro.core.unfused import build_unfused

from .common import mk, pallas_leg_row, time_fn

PALLAS_MAX_ROWS = 192  # interpret mode unrolls the grid at trace time


def run(sizes=((256, 256), (1024, 1024), (4096, 2048)), interpret=None):
    interpret = resolve_interpret(interpret)
    prog = normalization_program()
    gen = compile_program(prog, backend="jax")
    unfused = build_unfused(prog, per_pass_jit=True).fn     # leg A: autovec
    fusedvec_fn = jax.jit(lambda u: build_unfused(prog).fn(u=u)["nflux"])  # leg B
    rolling_fn = jax.jit(lambda u: gen.fn(u)["nflux"])       # leg C
    pallas_gen = compile_program(prog, backend="pallas", interpret=interpret)
    pallas_fn = jax.jit(lambda u: pallas_gen.fn(u=u)["nflux"])  # leg D
    rng = np.random.default_rng(0)
    rows = []
    for (nj, ni) in sizes:
        u = mk(rng, (nj, ni))
        t_a, a = time_fn(lambda u: unfused(u=u)["nflux"], u)
        t_b, b = time_fn(fusedvec_fn, u)
        t_c, c = time_fn(rolling_fn, u)
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        assert np.allclose(np.asarray(a), np.asarray(c), atol=1e-5)
        cells = nj * ni
        t_best = min(t_b, t_c)
        rows.append({
            "name": f"normalization_{nj}x{ni}",
            "us_per_call": t_best * 1e6,
            "derived": (
                f"unfused_us={t_a*1e6:.0f};fusedvec_us={t_b*1e6:.0f};"
                f"rolling_us={t_c*1e6:.0f};speedup={t_a/t_best:.2f}x;"
                f"passes=5->2;Mcells_s={cells/t_best/1e6:.0f}"
            ),
        })
    # Pallas leg (bounded size off-TPU; see module docstring)
    nj, ni = (min(s[0] for s in sizes), min(s[1] for s in sizes))
    if interpret:
        nj, ni = min(nj, PALLAS_MAX_ROWS), min(ni, 512)
    u = mk(rng, (nj, ni))
    ref = build_unfused(prog).fn(u=u)["nflux"]
    rows.append(pallas_leg_row(
        f"normalization_pallas_{nj}x{ni}", pallas_fn, ref, u,
        interpret=interpret, extra="nests=2;"))
    return rows
