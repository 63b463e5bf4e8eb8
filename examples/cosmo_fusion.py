"""COSMO diffusion micro-kernels through every HFAV backend (paper §5.3).

Shows: the fused single-nest schedule, the rolling-buffer storage plan
(ulap 2 rows + fy 2 rows — one row tighter than the paper's 5 thanks to
exact lead analysis), the generated JAX source, and the Pallas TPU
backend (interpret mode off the TPU).

    PYTHONPATH=src python examples/cosmo_fusion.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core import compile_program, explain
from repro.core.programs import cosmo_program
from repro.core.unfused import build_unfused
from repro.kernels.stencil2d import run_fused_stencil


def main():
    prog = cosmo_program()
    print(explain(prog))

    gen = compile_program(prog, backend="jax")
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((4, 48, 160)), jnp.float32)

    ref = build_unfused(prog).fn(u=u)["unew"]
    fused = gen.fn(u)["unew"]
    pallas = run_fused_stencil(prog, {"u": u})["unew"]

    e1 = float(jnp.abs(fused - ref).max())
    e2 = float(jnp.abs(pallas - ref).max())
    print(f"\nJAX rolling-buffer backend  max|err| = {e1:.2e}")
    print(f"Pallas VMEM backend            max|err| = {e2:.2e}")
    assert e1 < 1e-4 and e2 < 1e-4
    print("\nRolling buffers in the fused nest:")
    for key, vp in gen.plan.vars.items():
        if vp.kind == "rolling":
            print(f"  {vp.name}: {vp.stages} rows "
                  f"(contraction over {vp.contraction_dim})")


if __name__ == "__main__":
    main()
