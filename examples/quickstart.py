"""HFAV quickstart: declare kernels -> infer dataflow -> fuse -> run.

The 5-point Laplace stencil of the paper's Listing 1/Fig. 2, driven
through the whole engine and both backends (see docs/BACKENDS.md for
the dispatch rules).  Run:

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import compile_program, explain
from repro.core.programs import laplace5_program
from repro.core.unfused import build_unfused


def plan_dump(prog):
    """The rendered KernelPlan ``backend="auto"`` would hand the Pallas
    interpreter — `explain(prog, verbose=True)` appends it after the
    schedule and storage plan.  Doctested so the plan rendering (grid
    ranges, streaming windows, per-step reads at their leads, output
    trim rules) and the vectorization analysis (access classes, the
    redundant-load ratio of the overlapping 5-point reads, PV
    diagnostics, layout hints) cannot silently rot:

    >>> from repro.core.programs import laplace5_program
    >>> print(plan_dump(laplace5_program()))
    kernel plan: laplace5
      loop order: (j, i)
      call laplace5_n0: grid j=[-1, Nj-1)
        input cell: rows[0,+0] cols[0,+0] lead=1 stages=3
        step laplace5 @lead 0: reads [in_cell[j-1], in_cell[j+0], \
in_cell[j+1], in_cell[j+0], in_cell[j+0]] -> out:0
        out laplace_cell: external lead=0 rows[1,-1]
      goals: lap<-laplace_cell
    --- vmem estimate ---
      laplace5_n0:
        in_cell: sub(R+2) x pad(Ni+0) x 4B
        stream cell: 2 x R x pad(Ni+0) x 4B
        out laplace_cell: 2 x R x pad(Ni+0) x 4B
        seat laplace_cell: sub(R+p) x pad(Ni+0) x 4B where R > 1
        body: 6 x sub(R) x pad(Ni+0) x 4B
    --- vectorization ---
      access classes: aligned=2 shifted=4
      redundant-load ratio: 1.67
      window in_cell [laplace5_n0]: reuse 3/3 rows
      PV002 warning [laplace5_n0] in_cell: step laplace5 row j-1: no \
read of this group is lane-aligned (origins [1]) — every load crosses \
lanes
      PV002 warning [laplace5_n0] in_cell: step laplace5 row j+1: no \
read of this group is lane-aligned (origins [1]) — every load crosses \
lanes
      PV005 warning [laplace5_n0] laplace5: 5 contiguous reads over 3 \
resident row(s): overlapping shifted loads move 1.67x the unique \
elements
      hint realign_origin [laplace5_n0] in_cell: re-origin the \
resident window so the group gains an aligned anchor load
      hint shift_reuse [laplace5_n0] in_cell: replace overlapping \
loads of one resident row with one widened load plus in-register \
shifts
    --- layout apply ---
      apply mode: off
      every hint stays advisory (see the vectorization hints above)
    """
    report = explain(prog, verbose=True)
    return report.split("--- kernel plan ---\n", 1)[1]


def main():
    prog = laplace5_program()

    # `explain` also reports which backend `backend="auto"` would pick;
    # verbose=True appends the declarative KernelPlan the stencil
    # interpreter will execute (see plan_dump above).
    print("=== transformation report (paper's debugging output) ===")
    print(explain(prog, verbose=True))

    # backend="jax": emit fused, vectorized JAX source (inspectable).
    gen = compile_program(prog, backend="jax")
    print("\n=== generated JAX source (the paper's emitted code) ===")
    print(gen.source)

    rng = np.random.default_rng(0)
    cell = jnp.asarray(rng.standard_normal((64, 96)), jnp.float32)
    fused = gen.fn(cell)["lap"]
    ref = build_unfused(prog).fn(cell=cell)["lap"]
    err = float(jnp.abs(fused - ref).max())
    print(f"=== fused vs unfused max |err| = {err:.2e} ===")
    assert err < 1e-5

    # backend="pallas": the same schedule on the TPU stencil executor —
    # rolling buffers in VMEM, a tile of rows per grid step.  Off-TPU
    # it runs in interpret mode, so we validate on a small grid (the
    # grid unrolls at trace time); on a TPU it compiles with Mosaic.
    # double_buffer=True selects the explicit two-slot input-DMA pipeline.
    small = cell[:24, :]
    gen_p = compile_program(prog, backend="pallas")
    perr = float(jnp.abs(
        gen_p.fn(cell=small)["lap"]
        - build_unfused(prog).fn(cell=small)["lap"]).max())
    print(f"=== pallas vs unfused max |err| = {perr:.2e} ===")
    assert perr < 1e-5

    # backend="auto" (the default) probes Pallas viability per program
    # and falls back to the JAX backend when the executor rejects it.
    auto_gen = compile_program(prog)
    print(f"=== auto picked: {type(auto_gen).__name__} ===")


if __name__ == "__main__":
    main()
