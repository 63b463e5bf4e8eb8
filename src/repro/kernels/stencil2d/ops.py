"""Jitted wrapper for the fused stencil executor."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


def run_fused_stencil(program, arrays, *, interpret: Optional[bool] = None,
                      dtype=jnp.float32):
    """Compile `program` through the HFAV engine onto the Pallas backend
    and execute it on `arrays` (dict name -> jnp array).  ``interpret``
    defaults to interpret mode exactly where the default backend is not
    a TPU.  Compilation is cached by the engine's dispatch layer."""
    from repro.core.engine import compile_program

    gen = compile_program(program, backend="pallas", dtype=dtype,
                          interpret=interpret)
    return gen.fn(**arrays)
