"""Shared input synthesis for the cross-interpreter conformance tests.

Every interpreter executes the same :class:`~repro.core.plan.KernelPlan`
against the same synthesized inputs, so the helpers here derive array
shapes from the plan's own axiom shape contracts
(:class:`~repro.core.plan.AxiomPlan`: length along a dim is
``size + hi - lo``) rather than hard-coding per-program shapes.
"""
import jax.numpy as jnp

#: Concrete sizes for the loop dims the test programs use.  Deliberately
#: small, mutually distinct, and non-multiples of each other so grid
#: odometer bugs (wrong dim order, wrong modulus) cannot cancel out.
DIM = {"i": 20, "j": 7, "k": 4, "l": 3}


def sizes_for(kplan, dims: dict = DIM) -> dict:
    """``{size symbol: int}`` for a plan under the loop dims ``dims``
    (default the standard test dims)."""
    return {sym: dims.get(d, 3) for d, sym in kplan.dim_sizes}


def arrays_for(kplan, rng, dims: dict = DIM) -> dict:
    """Synthesize one input array per axiom of ``kplan`` at the loop
    dims ``dims``.

    Shapes come from the plan's axiom extents (outermost dim first,
    ``size + hi - lo`` per dim); values are standard-normal float32 so
    cancellation bugs don't hide behind all-ones inputs."""
    sizes = sizes_for(kplan, dims)
    arrs = {}
    for ax in kplan.axioms:
        ext = {d: (sym, lo, hi) for d, sym, lo, hi in ax.extents}
        shape = []
        for d in ax.dims:
            sym, lo, hi = ext[d]
            shape.append(sizes[sym] + hi - lo)
        arrs[ax.array] = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return arrs
