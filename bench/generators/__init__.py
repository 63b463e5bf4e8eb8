"""The general load generators of a timed window; a traffic file names one
by its ``generator`` key and gives its parameters.

Every generator module defines ``Generator(cell, seed, bench)`` with:

* ``setup() -> dict``: make the inputs from the seed, compile and warm
  every shape the window uses; returns ``{"compile_s": seconds}``;
* ``window(seconds) -> (end_to_end, attempted, failed, counters)``: the
  timed window, inside the ``bench.window`` span;
* ``release()``: free what only the program needed;
* ``check() -> dict``: the readings compared with the configuration's
  limits;
* ``notes(trace) -> list[str]``: lines printed before the result.
"""
from __future__ import annotations

import importlib

import jax


def load(name: str):
    """The ``Generator`` class of ``bench/generators/<name>.py``."""
    try:
        module = importlib.import_module(f"bench.generators.{name}")
    except ModuleNotFoundError as err:
        if err.name != f"bench.generators.{name}":
            raise
        raise ValueError(f"unknown generator {name!r}: no bench/generators/{name}.py") from None
    return module.Generator


def seed_key(seed: int):
    """A PRNG key of all the bits of ``seed``, which may exceed 32 bits."""
    seed %= 1 << 64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def random_arrays(shapes: dict, dtype, seed: int) -> dict:
    """Standard normal ``{name: array}`` of ``shapes`` made on the device in
    one jitted call from ``seed``."""
    names = sorted(shapes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        return {n: jax.random.normal(k, tuple(shapes[n]), dtype)
                for n, k in zip(names, keys)}

    return make(seed_key(seed))


def program_sizes(prog, config: dict) -> dict:
    """``{size symbol: int}`` of the program's axioms, from the configuration."""
    syms = {e.size for ax in prog.axioms for e in ax.extents.values()}
    missing = syms - set(config)
    if missing:
        raise ValueError(f"configuration lacks sizes {sorted(missing)}")
    return {s: int(config[s]) for s in syms}
