"""Run a fused nest in the dim order the device keeps its arrays in.

A fused nest streams its arrays with the loop order's second-innermost
dim as rows and its innermost dim as lanes, so it reads and writes
row-major arrays.  The device need not keep an input row-major: a TPU
keeps ``f32[80, 774, 1158]`` with the 774-long dim most major, because
80 rows fill whole (8, 128) tiles and 774 would not.  XLA then copies
the array into the kernel's layout on the way in and back on the way
out, which costs about as much device time as the kernel itself.

Three pieces remove those copies:

* :func:`device_major_to_minor` asks the backend for the default layout
  of an array's shape and dtype (cached per device kind, shape and
  dtype).  Off the TPU every layout is row-major.
* :func:`device_loop_order` turns the layouts of a plan's inputs into
  the loop order whose dims follow the device's: ``("j", "k", "i")`` for
  the array above.  It gives ``None`` where that is the plan's own
  order, where it would move the lane dim, or where two inputs disagree.
* :func:`permute_program` rewrites a :class:`~repro.core.rules.Program`
  under another loop order: every term's indices are reordered to it.
  The host half (:func:`device_ordered`) hands the permuted program's
  executor ``jnp.transpose`` views of the inputs and transposes each
  goal back; under the device's layout both are bitcasts.
"""
from __future__ import annotations

import functools
from dataclasses import replace
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from .dataflow import var_name
from .interpreters import resolve_sizes
from .plan import KernelPlan, PallasUnsupported
from .rules import Axiom, Goal, Param, Program
from .terms import Index, Ref, Term
from .tiling import call_tiling


def _dim(ix: Index) -> str:
    return ix.dim.rstrip("?")


def _permute_term(term: Term, old: Sequence[str],
                  new: Sequence[str]) -> Term:
    dims = [_dim(ix) for ix in term.ref.indices]
    if len(set(dims)) != len(dims) or not set(dims) <= set(old) or \
            dims != sorted(dims, key=old.index):
        raise ValueError(
            f"term {term} does not index its dims in the loop order "
            f"{tuple(old)}: it cannot be permuted")
    indices = tuple(sorted(term.ref.indices,
                           key=lambda ix: new.index(_dim(ix))))
    return Term(Ref(term.ref.name, indices), term.functors)


def permute_program(program: Program,
                    loop_order: Sequence[str]) -> Program:
    """``program`` under ``loop_order``, a permutation of its own: every
    term of every rule, axiom and goal indexes its dims in the new
    order, so each array's axes are permuted the same way everywhere.
    Extents, aliases, kernel bodies and the name are kept.  Raises
    ``ValueError`` for an order that is no permutation of the program's
    and for a term that does not index its dims in the program's order.
    Permuting back under the old order gives the program again."""
    old, new = tuple(program.loop_order), tuple(loop_order)
    if sorted(old) != sorted(new):
        raise ValueError(f"{new} is no permutation of {old}")

    def term(t: Term) -> Term:
        return _permute_term(t, old, new)

    def params(ps):
        return tuple(Param(p.name, term(p.pattern)) for p in ps)

    rules = [replace(r, inputs=params(r.inputs), outputs=params(r.outputs))
             for r in program.rules]
    return Program(
        rules=rules,
        axioms=[Axiom(term(a.term), dict(a.extents))
                for a in program.axioms],
        goals=[Goal(term(g.term), dict(g.extents), g.store_as)
               for g in program.goals],
        loop_order=new,
        aliases=list(program.aliases),
        name=program.name,
    )


def layout_device():
    """The device whose default layouts the host half follows: the
    default backend's first device."""
    return jax.devices()[0]


@functools.lru_cache(maxsize=None)
def _major_to_minor(device, shape: tuple[int, ...],
                    dtype: str) -> tuple[int, ...]:
    if device.platform == "cpu":
        return tuple(range(len(shape)))
    arg = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                               sharding=jax.sharding.SingleDeviceSharding(
                                   device))
    compiled = jax.jit(lambda x: x).lower(arg).compile()
    return tuple(compiled.input_formats[0][0].layout.major_to_minor)


def device_major_to_minor(shape: Sequence[int], dtype) -> tuple[int, ...]:
    """The axes of an array of ``shape`` and ``dtype`` from most major to
    most minor, as :func:`layout_device` lays it out by default; asked
    once per device, shape and dtype."""
    return _major_to_minor(layout_device(), tuple(int(n) for n in shape),
                           jnp.dtype(dtype).name)


def device_loop_order(kplan: KernelPlan,
                      arrays: dict) -> Optional[tuple[str, ...]]:
    """The loop order whose dims follow the order in which the device
    keeps ``arrays``, the plan's inputs: each input of two or more dims
    re-sequences its own dims, in the slots they hold in the plan's loop
    order, from most major to most minor.  ``None`` where that is the
    plan's own order, where it would move the lane (innermost) dim, or
    where two inputs disagree."""
    loop_order = tuple(kplan.loop_order)
    order = None
    for ax in kplan.axioms:
        if len(ax.dims) < 2:
            continue
        arr = arrays[ax.array]
        m2m = device_major_to_minor(jnp.shape(arr), jnp.result_type(arr))
        device_dims = iter(ax.dims[a] for a in m2m)
        mine = tuple(next(device_dims) if d in ax.dims else d
                     for d in loop_order)
        if order is not None and mine != order:
            return None
        order = mine
    if order is None or order == loop_order or order[-1] != loop_order[-1]:
        return None
    return order


def _axes(src: Sequence[str], dst: Sequence[str]) -> tuple[int, ...]:
    """``jnp.transpose`` axes taking an array indexed by ``src`` to one
    indexed by ``dst``."""
    return tuple(src.index(d) for d in dst)


def _rows(kplan: KernelPlan, sizes: dict, dtype) -> list[int]:
    """Rows R each grid step of each stencil call of ``kplan`` computes
    at ``sizes`` (``{size symbol: int}``)."""
    dim_sym = dict(kplan.dim_sizes)
    call_sizes = tuple(sizes[dim_sym[d]] for d in kplan.loop_order)
    itemsize = jnp.dtype(dtype).itemsize
    return [call_tiling(c, call_sizes, itemsize).rows
            for c in kplan.calls if c.has_grid]


def _keeps_rows(kplan: KernelPlan, pplan: KernelPlan, sizes: dict,
                dtype) -> bool:
    """Whether ``pplan`` tiles at ``sizes``, and runs more than one row a
    grid step wherever ``kplan`` does."""
    try:
        prows = _rows(pplan, sizes, dtype)
    except PallasUnsupported:
        return False
    return min(prows, default=1) > 1 or \
        max(_rows(kplan, sizes, dtype), default=1) == 1


def device_ordered(program: Program, kplan: KernelPlan, fn: Callable,
                   build: Callable, *, dtype) -> Callable:
    """Wrap ``fn``, the host half executing ``kplan`` (the plan of
    ``program``), so that each call runs in the loop order
    :func:`device_loop_order` finds for its inputs.  ``build(order)``
    gives the ``(plan, host half)`` of the program permuted to ``order``
    (:func:`permute_program`), or ``None`` where that program is refused;
    it runs once per order.  A call keeps ``fn`` where no order is found,
    where the permuted plan is refused, or where it would run one row a
    grid step where ``kplan`` runs more."""
    built: dict = {}
    goal_dims = {g.store_as or var_name(g.term):
                 tuple(map(_dim, g.term.ref.indices)) for g in program.goals}

    def run(**arrays):
        order = device_loop_order(kplan, arrays)
        if order is not None and order not in built:
            built[order] = build(order)
        alt = built.get(order)
        if alt is None:
            return fn(**arrays)
        pplan, pfn = alt
        if not _keeps_rows(kplan, pplan, resolve_sizes(kplan, arrays),
                           dtype):
            return fn(**arrays)
        views = dict(arrays)
        for ax in kplan.axioms:
            pdims = tuple(sorted(ax.dims, key=order.index))
            if pdims != ax.dims:
                views[ax.array] = jnp.transpose(arrays[ax.array],
                                                _axes(ax.dims, pdims))
        outs = pfn(**views)
        for store, dims in goal_dims.items():
            pdims = tuple(sorted(dims, key=order.index))
            if pdims != dims:
                outs[store] = jnp.transpose(outs[store], _axes(pdims, dims))
        return outs

    return run
