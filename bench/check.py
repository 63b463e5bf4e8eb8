"""The comparison that decides ``correct``.

A served or swept answer is compared with the plain reference of its
configuration's program (``bench/references/<program>.py``), computed
in the configuration's dtype.  The number compared is the widest
relative gap over every element of every goal store::

    rel_err = max |got - ref| / (|ref| + mean |ref|)

The mean in the denominator keeps a near-zero reference element from
dividing by nearly nothing, while a misplaced row, a stale answer or a
lower precision errs by the data's own magnitude.  The limit of each
configuration is in its file (``limits.rel_err``) with the readings it
was set from in ``PERF.md``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


@jax.jit
def _rel_err(got, want):
    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    scale = jnp.abs(want) + jnp.mean(jnp.abs(want))
    return jnp.max(jnp.abs(got - want) / scale)


def rel_err(got: dict, want: dict) -> float:
    """Widest relative gap of ``got`` against ``want`` over every store;
    ``inf`` where a store is missing or its shape or dtype differs, and
    NaN where ``got`` holds one."""
    widest = 0.0
    for name, ref in want.items():
        arr = got.get(name)
        if arr is None or tuple(arr.shape) != tuple(ref.shape) \
                or jnp.dtype(arr.dtype) != jnp.dtype(ref.dtype):
            return math.inf
        err = float(_rel_err(jnp.asarray(arr), ref))
        if math.isnan(err):
            return err
        widest = max(widest, err)
    return widest


def worst(readings) -> float:
    """The largest reading, NaN where any is NaN, ``inf`` where there is
    none."""
    values = list(readings)
    if any(math.isnan(v) for v in values):
        return math.nan
    return max(values, default=math.inf)


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``: correct where every
    reading is at or under its limit (a NaN never is)."""
    shown = {k: {"value": readings[k], "limit": limits[k]} for k in limits}
    return all(readings[k] <= limits[k] for k in limits), shown
