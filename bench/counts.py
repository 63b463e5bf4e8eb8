"""What a program needs at a cell's sizes, counted from its declaration.

The counts come from the program's axioms and goals (its inputs and
outputs) and never from a kernel plan, so no change to how the program
is planned, fused or tiled can move them.
"""
from __future__ import annotations

import math


def _dim(d: str) -> str:
    return d.rstrip("?")


def input_shapes(prog, sizes: dict) -> dict:
    """``{array: shape}`` of every axiom: ``size + hi - lo`` along each dim."""
    shapes = {}
    for ax in prog.axioms:
        exts = [ax.extents[_dim(d)] for d in ax.term.ref.dims]
        shapes[ax.term.ref.name] = tuple(sizes[e.size] + e.hi - e.lo for e in exts)
    return shapes


def least_bytes(prog, sizes: dict, itemsize: int) -> int:
    """The fewest HBM bytes one sweep can move: every axiom array read
    once, and every goal store's valid region (``[lo, size + hi)`` per
    dim) written once."""
    total = sum(math.prod(s) for s in input_shapes(prog, sizes).values())
    for g in prog.goals:
        exts = [g.extents[_dim(d)] for d in g.term.ref.dims]
        total += math.prod(sizes[e.size] + e.hi - e.lo for e in exts)
    return total * itemsize


def grid_steps(kplan, sizes: dict) -> int:
    """Grid steps over all stencil calls of a kernel plan."""
    sym = dict(kplan.dim_sizes)
    total = 0
    for c in kplan.calls:
        if not c.has_grid:
            continue
        steps = sizes[sym[c.row_dim]] + c.x_hi_off - c.x_lo
        for g, lo, hi in zip(c.grid[:-1], c.outer_lo, c.outer_hi_off):
            steps *= sizes[sym[g.dim]] + hi - lo
        total += steps
    return total
