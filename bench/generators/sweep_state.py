"""Back-to-back fused sweeps of one physical state: the sweep generator
(:mod:`bench.generators.sweep`) with inputs that a compressible-flow
step can take.  Standard-normal fields are no such state: a negative
density or pressure has no sound speed.

The configuration's ``state`` key gives the draw: with ``z1 .. z4``
standard normal per cell, density ``exp(log_rho_sd z1)``, velocities
``vel_sd z2`` and ``vel_sd z3``, pressure ``exp(log_p_sd z4)``; the
conserved fields are ``rho``, ``mu = rho u``, ``mv = rho v`` and
``en = p / (gamma - 1) + rho (u^2 + v^2) / 2``, made on the device in
one jitted call from the seed.

Traffic parameters: those of :mod:`bench.generators.sweep`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import counts
from bench.generators import seed_key
from bench.generators.sweep import Generator as SweepGenerator


def physical_state(shape, state: dict, dtype, seed: int) -> dict:
    """``{"rho", "mu", "mv", "en"}`` of ``shape`` drawn from ``seed`` as
    the ``state`` parameters say."""
    gamma = float(state["gamma"])
    a, b, c = (float(state[k]) for k in ("log_rho_sd", "vel_sd", "log_p_sd"))

    @jax.jit
    def make(key):
        z = jax.random.normal(key, (4, *shape), jnp.float32)
        rho = jnp.exp(a * z[0])
        u = b * z[1]
        v = b * z[2]
        p = jnp.exp(c * z[3])
        en = p / (gamma - 1.0) + 0.5 * rho * (u * u + v * v)
        return {k: x.astype(dtype) for k, x in
                (("rho", rho), ("mu", rho * u), ("mv", rho * v), ("en", en))}

    return make(seed_key(seed))


class Generator(SweepGenerator):
    def __init__(self, cell, seed: int, bench):
        super().__init__(cell, seed, bench)
        self.state = cell.config["state"]

    def make_inputs(self) -> list:
        """The one seeded state every sweep reads, made on the device."""
        shapes = set(counts.input_shapes(self.prog, self.sizes).values())
        if len(shapes) != 1:
            raise ValueError(f"the state's fields differ in shape: {sorted(shapes)}")
        return [physical_state(shapes.pop(), self.state, self.dtype, self.seed)]
