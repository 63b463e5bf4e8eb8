"""The plan-interpreter registry: N executors behind one KernelPlan IR.

HFAV's core claim is that one declarative kernel description lowers to
multiple efficient executable forms (the cjit emu/avx2/avx512 shape).
This module is that seam for the KernelPlan IR: an interpreter is a
**pluggable registration** — a name mapped to an
:class:`InterpreterSpec` carrying a declared *capability set* (which
:data:`~repro.core.plan.PLAN_FEATURES` tags it can execute), the
execution *flags* it honors, and a ``build_call`` that concretizes one
:class:`~repro.core.plan.CallPlan` for a problem size.  The engine's
backend dispatch (:func:`repro.core.engine.compile_program`) resolves
any non-``"jax"``/``"auto"`` backend name through
:func:`get_interpreter`, so new executors (Pallas-Triton, compiled TPU
variants) drop in as one registration — and the golden corpus,
round-trip suite, differential fuzzer, and conformance sweep
(``tests/test_interp_conformance.py``) cover them automatically.

Two interpreters self-register on first use:

* ``"pallas"`` — the Pallas TPU stencil interpreter
  (:mod:`repro.kernels.stencil2d.kernel`): VMEM scratch windows,
  BlockSpec or double-buffered DMA row streaming;
* ``"interp_jax"`` — the pure-JAX plan interpreter
  (:mod:`repro.core.interp_jax`): the same plan semantics transliterated
  onto a ``lax.fori_loop`` over the linearized grid, replacing the
  legacy hand-written ``codegen_jax`` emitter on the plan-covered path.

Every ``build_call`` must honor the **output contract** of the Pallas
reference implementation — ``external`` outputs goal-shaped
``(*N_outer, Nj, Ni)``, every element outside the goal's rows, planes
and lanes equal to the output's ``fill``; other row outputs
``(*grid, rows, ni)`` with ``rows >= steps_j`` (rows past ``steps_j``
are padding); carried accumulators ``(1, width)``, kept-prefix
accumulators ``(*grid[:n_kept], 1, width)`` — because the host half here
(:func:`execute_plan`: size resolution through axiom shape contracts,
environment threading, and the :func:`_assemble` trim/seat/lane-reduce
rules for the outputs that are not ``external``) is shared by every
interpreter verbatim.

Capability mismatches raise the typed :class:`PlanUnsupported` (a
:class:`~repro.core.plan.PallasUnsupported` subclass, so existing
``auto``-fallback handling applies unchanged); unknown names raise
``ValueError`` listing what *is* registered.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..trace import count, span
from .plan import (PLAN_FEATURES, CallPlan, KernelPlan, OutputPlan,
                   PallasUnsupported)
from .plancheck import (call_vmem, row_tile, scoped_vmem_limit,
                        seat_geometry)
from .runtime import lane_reduce


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an ``interpret`` flag: ``None`` means interpret mode
    exactly where JAX's default backend is not a TPU, so a TPU never
    runs the CPU interpreter unless asked to.  An explicit bool stands
    (``False`` compiles for a described TPU from a CPU host)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


class PlanUnsupported(PallasUnsupported):
    """A validated plan demands features outside an interpreter's
    declared capability set — a typed refusal (never a miscompile),
    raised by :func:`check_capabilities` before anything builds."""


@dataclass(frozen=True)
class InterpreterSpec:
    """One registered plan interpreter.

    ``build_call(call, sizes, dtype, interpret=..., double_buffer=...)``
    concretizes a :class:`~repro.core.plan.CallPlan` to
    ``(fn, steps_j)`` under the shared output contract (see the module
    docstring).  ``capabilities`` is the subset of
    :data:`~repro.core.plan.PLAN_FEATURES` the interpreter executes;
    ``flags`` names the execution flags it actually honors (subset of
    ``{"interpret", "double_buffer"}``) so the engine can normalize
    un-honored flags out of its cache keys.  ``layout_aware`` declares
    that ``build_call`` executes the constructs the LayoutApply pass
    (:mod:`repro.core.layoutapply`) writes when it realizes the plan's
    advisory :attr:`~repro.core.plan.KernelPlan.layout_hints`
    (carried-vector slots, ``align_pad``, ``lane_block``); the engine
    only runs the pass for layout-aware interpreters, and
    layout-oblivious ones execute hinted plans unchanged."""

    name: str
    build_call: Callable = field(compare=False)
    capabilities: frozenset = frozenset()
    flags: frozenset = frozenset()
    description: str = ""
    layout_aware: bool = False


_REGISTRY: dict[str, InterpreterSpec] = {}

#: Modules that register the built-in interpreters at import time,
#: loaded lazily on first registry use (module-level imports here would
#: be circular: the Pallas interpreter imports the plan IR from
#: repro.core).
_BUILTIN_MODULES = ("repro.kernels.stencil2d.kernel",
                    "repro.core.interp_jax")
_builtins_loaded = False


def _ensure_builtins() -> None:
    global _builtins_loaded
    if _builtins_loaded:
        return
    _builtins_loaded = True
    with span("hfav.load_interpreters"):
        for mod in _BUILTIN_MODULES:
            importlib.import_module(mod)


def register_interpreter(spec: InterpreterSpec) -> None:
    """Register (or replace) a plan interpreter under ``spec.name``.

    Unknown capability tags are rejected immediately — a typo'd tag
    would otherwise silently widen what the capability check lets
    through."""
    bad = spec.capabilities - PLAN_FEATURES
    if bad:
        raise ValueError(
            f"interpreter {spec.name!r} declares unknown capability "
            f"tags {sorted(bad)}; known tags: {sorted(PLAN_FEATURES)}")
    _REGISTRY[spec.name] = spec


def unregister_interpreter(name: str) -> None:
    """Remove a registered interpreter (test isolation helper)."""
    _REGISTRY.pop(name, None)


def registered_interpreters() -> tuple[str, ...]:
    """Sorted names of every registered interpreter (built-ins are
    loaded on first call)."""
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


def get_interpreter(name: str) -> InterpreterSpec:
    """Resolve a registered interpreter by name; unknown names raise
    ``ValueError`` listing what is registered."""
    _ensure_builtins()
    spec = _REGISTRY.get(name)
    if spec is None:
        raise ValueError(
            f"unknown plan interpreter {name!r}; registered: "
            f"{registered_interpreters()}")
    return spec


def check_capabilities(spec: InterpreterSpec, kplan: KernelPlan) -> None:
    """Raise :class:`PlanUnsupported` when ``kplan`` demands feature
    tags outside ``spec.capabilities`` (see
    :meth:`~repro.core.plan.KernelPlan.features`)."""
    missing = kplan.features() - spec.capabilities
    if missing:
        raise PlanUnsupported(
            f"plan {kplan.program!r} requires features {sorted(missing)} "
            f"outside interpreter {spec.name!r} capabilities")


# ---------------------------------------------------------------------------
# Shared build-time plan checks (every interpreter's build_call prologue)
# ---------------------------------------------------------------------------

def require_linked_fns(call: CallPlan) -> None:
    """Reject a call whose step/host/reduce fn indices point past its
    fn table — the signature of a deserialized plan that was never
    re-linked to its kernel callables."""
    fn_refs = [s.fn_idx for s in call.steps]
    fn_refs += [h.fn_idx for h in call.host_pre + call.host_post]
    fn_refs += [o.reduce_idx for o in call.outputs
                if o.reduce_idx is not None]
    if fn_refs and max(fn_refs) >= len(call.fns):
        raise ValueError(
            f"call {call.name}: plan references fn index {max(fn_refs)} "
            f"but the fn table has {len(call.fns)} entries — a "
            f"deserialized plan must re-link its kernel callables "
            f"(KernelPlan.from_dict / repro.core.plan.fn_from_spec)")


def require_hazard_free(call: CallPlan) -> None:
    """Reject the hazards no interpreter can execute meaningfully.

    This duplicates only the *certain* subset of the static analyzer
    (:mod:`repro.core.plancheck`) — reads whose mod-``stages`` slot
    arithmetic is guaranteed to alias a different row/plane, and local
    reads with no preceding write (a ``KeyError`` inside the traced
    kernel body otherwise).  The full analyzer additionally proves
    halo coverage and warm-up validity; run ``scripts/plan_lint.py``
    or ``compile_program(check_plans="error")`` for those."""
    if not call.has_grid:
        return
    windows = {w.name: w for w in call.windows}
    inputs = {f"in_{i.name}": i for i in call.inputs if not i.scalar}
    # carried-vector loads are window reads too: the fresh load each
    # grid step must hit a live slot (``vec:`` register reads
    # themselves are slot-bounded by KernelPlan.validate)
    for v in call.vloads:
        ispec = inputs.get(v.src)
        if ispec is None:
            continue  # validate() rejects non-input vload sources
        if not ispec.plane:
            if not (ispec.lead - ispec.stages < v.j_off <= ispec.lead):
                raise ValueError(
                    f"call {call.name}: vload {v.name} reads row "
                    f"j{v.j_off:+d} of {v.src}; the mod-slot arithmetic "
                    f"aliases it outside "
                    f"(j{ispec.lead - ispec.stages:+d}, "
                    f"j{ispec.lead:+d}] (PlanCheck PC002/PC005)")
        elif not (ispec.p_lead - ispec.p_stages
                  < v.p_off <= ispec.p_lead):
            raise ValueError(
                f"call {call.name}: vload {v.name} reads plane "
                f"p{v.p_off:+d} of {v.src}; the mod-slot arithmetic "
                f"aliases it outside "
                f"(p{ispec.p_lead - ispec.p_stages:+d}, "
                f"p{ispec.p_lead:+d}] (PlanCheck PC002/PC005)")
    produced_lead: dict[str, int] = {}
    local_seen: set[str] = set()
    for step in call.steps:
        for rd in step.reads:
            if rd.src.startswith("local:"):
                if rd.src[6:] not in local_seen:
                    raise ValueError(
                        f"call {call.name}: step {step.op} reads "
                        f"{rd.src} before any step writes it "
                        f"(PlanCheck PC001)")
                continue
            lead = stages = None
            ispec = inputs.get(rd.src)
            if ispec is not None and not ispec.plane:
                lead, stages = ispec.lead, ispec.stages
            elif ispec is not None and rd.p_off != ispec.p_lead:
                if not (ispec.p_lead - ispec.p_stages
                        < rd.p_off <= ispec.p_lead):
                    raise ValueError(
                        f"call {call.name}: step {step.op} reads plane "
                        f"p{rd.p_off:+d} of {rd.src}; the mod-slot "
                        f"arithmetic aliases it outside "
                        f"(p{ispec.p_lead - ispec.p_stages:+d}, "
                        f"p{ispec.p_lead:+d}] (PlanCheck PC002/PC005)")
            w = windows.get(rd.src)
            if w is not None and not w.plane and rd.src in produced_lead:
                lead, stages = produced_lead[rd.src], w.stages
            if lead is not None and not (lead - stages < rd.j_off <= lead):
                raise ValueError(
                    f"call {call.name}: step {step.op} reads row "
                    f"j{rd.j_off:+d} of {rd.src}; the mod-slot "
                    f"arithmetic aliases it outside "
                    f"(j{lead - stages:+d}, j{lead:+d}] "
                    f"(PlanCheck PC002/PC005)")
        for targets in step.writes:
            for kind, tgt in targets:
                if kind == "local":
                    local_seen.add(str(tgt))
                elif kind == "buf":
                    produced_lead.setdefault(str(tgt), step.lead)


# ---------------------------------------------------------------------------
# The shared host half: size resolution, environment threading, output
# assembly (the plan's trim/seat rules for every output kind but
# ``external``, which the interpreter seats itself) — identical for
# every interpreter because every build_call honors the same output
# contract.
# ---------------------------------------------------------------------------

def _lane_permute(arr, p, inverse: bool = False):
    """Apply one size-specialized :class:`~repro.core.plan.LanePass`
    along the last axis: de-interleave ``old col c -> (c % stride) *
    (width // stride) + c // stride`` (``inverse=True`` undoes it).
    The lane width is asserted at runtime — the permutation was
    specialized to it by the LayoutApply pass."""
    if arr.shape[-1] != p.width:
        raise ValueError(
            f"lane pass on {p.array!r}: array lane width "
            f"{arr.shape[-1]} != the size-specialized pass width "
            f"{p.width}")
    lead = arr.shape[:-1]
    m = p.width // p.stride
    if inverse:
        return arr.reshape(*lead, p.stride, m).swapaxes(-1, -2) \
                  .reshape(*lead, p.width)
    return arr.reshape(*lead, m, p.stride).swapaxes(-1, -2) \
              .reshape(*lead, p.width)


def _run_host(call: CallPlan, hs, env: dict) -> None:
    vals = call.fns[hs.fn_idx](*[env[n] for n in hs.reads])
    if len(hs.writes) == 1:
        vals = (vals,)
    for name, val in zip(hs.writes, vals):
        env[name] = val


def _outer_trim(out: OutputPlan, call: CallPlan, n_outs: tuple[int, ...],
                n_dims: int) -> tuple[slice, ...]:
    """Slices dropping warm-up/drain tiles of the first ``n_dims`` outer
    grid dims, keeping the output's canonical extent ``[lo, N_d + hi)``
    (a producer running ``outer_lead`` tiles ahead wrote its blocks that
    many tiles early)."""
    o_lo = call.outer_lo
    idx = []
    for d in range(n_dims):
        lead = out.outer_lead[d] if out.outer_lead else 0
        s0 = out.outer_lo[d] - lead - o_lo[d]
        cnt = n_outs[d] + out.outer_hi[d] - out.outer_lo[d]
        idx.append(slice(s0, s0 + cnt))
    return tuple(idx)


def _outer_seat(out: OutputPlan, n_outs: tuple[int, ...],
                n_dims: int) -> tuple[slice, ...]:
    """Slices seating a trimmed value at its goal origin inside
    full-size ``[0, N_d)`` outer dims."""
    return tuple(
        slice(out.outer_lo[d], n_outs[d] + out.outer_hi[d])
        for d in range(n_dims)
    )


def _assemble(call: CallPlan, out: OutputPlan, dev, nj: int, ni: int,
              n_outs: tuple[int, ...], dtype):
    """Map one device output back to its environment array.  An
    ``external`` output comes back goal-shaped from the interpreter and
    is handed on as it is; the other kinds come back in the grid's own
    frame: trim warm-up/drain rows and tiles, re-seat goal origins,
    lane-reduce accumulators whose vector dim was folded."""
    if out.kind == "external":
        if dev.shape != (*n_outs, nj, ni):
            raise ValueError(
                f"call {call.name}: external output {out.name} came back "
                f"{dev.shape}, not goal-shaped {(*n_outs, nj, ni)}")
        return dev
    n_out = call.n_outer
    reduce_fn = call.fns[out.reduce_idx] if out.reduce_idx is not None \
        else None
    if out.kind == "acc":
        if out.n_kept:
            # (*kept grid tiles, 1, width): one combined row per kept tile
            part = dev[_outer_trim(out, call, n_outs, out.n_kept)
                          + (0,)]
            if reduce_fn is not None:
                part = lane_reduce(reduce_fn,
                                   jnp.moveaxis(part, -1, 0),
                                   out.reduce_init)
            kept_exact = all(
                out.outer_lo[d] == 0 and out.outer_hi[d] == 0
                for d in range(out.n_kept))
            if kept_exact:
                return part
            shape = tuple(n_outs[:out.n_kept]) + part.shape[out.n_kept:]
            seat = _outer_seat(out, n_outs, out.n_kept) \
                + (slice(None),) * (part.ndim - out.n_kept)
            return jnp.zeros(shape, dtype).at[seat].set(part)
        row = dev[0]
        if reduce_fn is not None:
            return lane_reduce(reduce_fn, row, out.reduce_init)
        return row
    t0 = out.j_lo - (call.x_lo + out.lead)
    nrows = nj + out.j_hi - out.j_lo
    otrim = _outer_trim(out, call, n_outs, n_out)
    if out.kind == "acc_rows":
        # one identity-padded partial-accumulator row per row position:
        # trim, fold the lanes, seat at the goal origin
        part = dev[otrim + (slice(t0, t0 + nrows), slice(None))]
        vals = lane_reduce(reduce_fn, jnp.moveaxis(part, -1, 0),
                           out.reduce_init)
        res = jnp.zeros((*n_outs, nj), dtype)
        return res.at[_outer_seat(out, n_outs, n_out)
                      + (slice(out.j_lo, nj + out.j_hi),)].set(vals)
    w = ni + out.i_hi - out.i_lo
    return dev[otrim + (slice(t0, t0 + nrows),
                           slice(out.i_lo, out.i_lo + w))]


def _grid_steps(call: CallPlan, n_outs: tuple[int, ...], nj: int, ni: int,
                dtype, double_buffer: bool) -> tuple[int, int, int, int]:
    """Grid steps of one stencil call, the rows R each computes
    (:func:`repro.core.plancheck.row_tile`), the VMEM the call needs at
    that R (:func:`repro.core.plancheck.call_vmem`) and the scoped limit
    passed for it, 0 for the compiler's default
    (:func:`repro.core.plancheck.scoped_vmem_limit`), as the Pallas
    ``build_call`` lays out its grid
    (:func:`repro.core.plancheck.seat_geometry`) and sizes its VMEM."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = row_tile(call, nj, ni, itemsize, double_buffer)
    steps = seat_geometry(call, n_outs, nj, rows, itemsize).steps
    need = call_vmem(call, nj, ni, itemsize, double_buffer, rows=rows)["total"]
    return steps, rows, need, scoped_vmem_limit(need) or 0


def execute_plan(kplan: KernelPlan, *, interpreter: str = "pallas",
                 dtype=jnp.float32, interpret: Optional[bool] = None,
                 double_buffer: bool = False):
    """Build the host callable executing a full :class:`KernelPlan` on
    the named registered interpreter.

    The returned function takes the program's external arrays as keyword
    arguments and returns ``{store name: array}`` for every goal.  It
    resolves runtime dim sizes through the plan's axiom shape contracts,
    runs each :class:`CallPlan` (host prologue, the interpreter's
    ``build_call``, output assembly, host epilogue) in order, and
    threads intermediate arrays through the environment.  The capability
    check runs here, so a plan outside the interpreter's declared
    feature set raises :class:`PlanUnsupported` before anything builds.
    ``interpret`` (resolved by :func:`resolve_interpret`) and
    ``double_buffer`` are forwarded to ``build_call``; interpreters that
    don't honor a flag accept and ignore it.

    The span ``hfav.build_call`` (attributes ``call``, ``grid_steps``,
    ``row_tile``, ``vmem_need_bytes`` and ``vmem_limit_bytes``) and the
    counters ``hfav.grid_steps``, ``hfav.row_tile`` (the rows each grid
    step computes), ``hfav.vmem_need_bytes`` (the VMEM model's need at
    that row tile) and ``hfav.vmem_limit_bytes`` (the scoped VMEM limit
    passed, 0 for the compiler's default), ``hfav.outputs_seated`` (the
    call's ``external`` outputs, which the interpreter writes at their
    goal seat) and ``hfav.outputs_trimmed`` (its other outputs, which
    :func:`_assemble` trims), each added once per built call
    (:mod:`repro.trace`), mark each stencil call as ``fn``'s Python
    runs: under ``jax.jit`` once per trace, never per compiled call."""
    spec = get_interpreter(interpreter)
    interpret = resolve_interpret(interpret)
    check_capabilities(spec, kplan)
    dim_sym = dict(kplan.dim_sizes)
    inner = kplan.loop_order[-1]
    jdim = kplan.loop_order[-2]
    outer_dims = kplan.loop_order[:-2]
    input_names = sorted({ax.array for ax in kplan.axioms})

    def fn(**arrays):
        sizes: dict[str, int] = {}
        for ax in kplan.axioms:
            arr = arrays[ax.array]
            ext = {d: (sym, lo, hi) for d, sym, lo, hi in ax.extents}
            for axis, d in enumerate(ax.dims):
                e = ext.get(d)
                if e is not None and e[0] not in sizes:
                    sizes[e[0]] = arr.shape[axis] - (e[2] - e[1])
        nj = sizes[dim_sym[jdim]]
        ni = sizes[dim_sym[inner]]
        n_outs = tuple(sizes[dim_sym[d]] for d in outer_dims)
        env: dict[str, jnp.ndarray] = {
            name: arrays[name] for name in input_names
        }
        for p in kplan.pre_passes:
            env[p.array] = _lane_permute(jnp.asarray(env[p.array], dtype),
                                         p)
        for cp in kplan.calls:
            for hs in cp.host_pre:
                _run_host(cp, hs, env)
            if cp.has_grid:
                steps, rows, need, limit = _grid_steps(
                    cp, n_outs, nj, ni, dtype, double_buffer)
                count("hfav.grid_steps", steps)
                count("hfav.row_tile", rows)
                count("hfav.vmem_need_bytes", need)
                count("hfav.vmem_limit_bytes", limit)
                seated = sum(o.kind == "external" for o in cp.outputs)
                count("hfav.outputs_seated", seated)
                count("hfav.outputs_trimmed", len(cp.outputs) - seated)
                with span("hfav.build_call", call=cp.name, grid_steps=steps,
                          row_tile=rows, vmem_need_bytes=need,
                          vmem_limit_bytes=limit):
                    pcall, _ = spec.build_call(cp, (*n_outs, nj, ni), dtype,
                                               interpret=interpret,
                                               double_buffer=double_buffer)
                    args = []
                    for ispec in cp.inputs:
                        v = jnp.asarray(env[ispec.name], dtype)
                        if ispec.scalar:
                            v = v.reshape((1, 1))
                        args.append(v)
                    outs = pcall(*args)
                if not isinstance(outs, (list, tuple)):
                    outs = [outs]
                for out, pout in zip(cp.outputs, outs):
                    env[out.name] = _assemble(cp, out, pout, nj, ni,
                                              n_outs, dtype)
            for hs in cp.host_post:
                _run_host(cp, hs, env)
        for p in kplan.post_passes:
            env[p.array] = _lane_permute(env[p.array], p, inverse=True)
        return {store: env[var] for store, var in kplan.goal_outputs}

    return fn
