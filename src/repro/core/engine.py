"""HFAV engine driver: program -> inference -> dataflow -> fusion ->
storage analysis -> backend dispatch.  The public entry point of the
paper's contribution.

:func:`compile_program` runs the shared analysis pipeline once and then
dispatches to a backend:

* ``backend="jax"`` — emit fused, vectorized JAX source
  (:mod:`repro.core.codegen_jax`), returning :class:`Generated`;
* ``backend="<interpreter>"`` — any name in the **plan-interpreter
  registry** (:mod:`repro.core.interpreters`): lower the schedule to
  the declarative :class:`~repro.core.plan.KernelPlan` IR
  (:func:`repro.core.codegen_pallas.plan_pallas`, the planner) and hand
  it to that registered interpreter through the shared host half
  (:func:`repro.core.interpreters.execute_plan`), returning
  :class:`PallasGenerated`; raises :class:`PallasUnsupported` for
  programs outside the planner's shape and the typed
  :class:`~repro.core.interpreters.PlanUnsupported` subclass for plans
  outside the interpreter's declared capability set.  Built-ins:
  ``"pallas"`` (the Pallas TPU stencil interpreter) and ``"interp_jax"``
  (the pure-JAX plan interpreter, :mod:`repro.core.interp_jax`);
* ``backend="auto"`` (default) — probe Pallas applicability and fall
  back to JAX.  Any single-nest schedule over a (row, vector) loop order
  — including reductions (carried, kept-prefix and row-kept), outer
  grids, outer-dim stencil halos (plane windows for streamed inputs
  *and* same-nest produced variables), and cross-row materialized reads
  — goes to the stencil interpreter;
  split (multi-nest) schedules take the JAX backend unless the program
  name has been registered as a measured Pallas win with
  :func:`register_pallas_split_win` (benchmark legs feed this table from
  real-TPU ``interpret=False`` timings).  The probe itself is safe:
  shapes the planner still rejects raise :class:`PallasUnsupported`
  during lowering and silently fall back to JAX.

The full routing rules, the cache keys, and the table of remaining
``PallasUnsupported`` shapes live in docs/BACKENDS.md.

Compiled results are cached at two levels: a fast path keyed on
(program signature, backend, dtype, interpret) — with flags an
interpreter declares it does not honor normalized out — and,
for every registry backend, a **plan-level** cache keyed on
(interpreter name, :meth:`KernelPlan.cache_key`), so two
differently-built programs that lower to structurally equal plans
share one compiled interpreter while two interpreters executing the
*same* plan never collide.  The
plan-level cache is LRU-bounded (:func:`set_plan_cache_cap`) and, when
``plan_cache_dir=...`` is passed, becomes the L1 over a durable
on-disk L2 (:mod:`repro.core.plancache`): a process that finds its
program's serialized plan on disk builds the interpreter straight from
the loaded IR and never invokes the analysis pipeline at all.
"""
from __future__ import annotations

import functools
import os
import warnings
from collections import OrderedDict
from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..trace import count, span
from .codegen_jax import Generated, generate
from .codegen_pallas import (PallasGenerated, PallasUnsupported,
                             plan_pallas)
from .dataflow import build_dataflow
from .dimorder import device_ordered, permute_program
from .fusion import Unfusable, fuse_inest_dag
from .infer import InferenceError, infer
from .interpreters import (get_interpreter, registered_interpreters,
                           resolve_interpret)
from .layoutapply import render_apply, resolve_apply_mode
from .layoutapply import apply_layout as run_layout_pass
from .plan import KernelPlan
from .plan import fn_key as _fn_key
from .plancheck import (PlanCheckError, PlanCheckWarning, check_plan,
                        has_errors, render_vmem, resolve_check_mode,
                        vmem_bytes, vmem_budget, vmem_report)
from .reuse import StoragePlan, analyze_storage
from .rules import Program
from .vecscan import auto_vec_reject, scan_plan

#: The built-in backend names.  ``compile_program`` additionally
#: accepts any name in the plan-interpreter registry
#: (:func:`repro.core.interpreters.registered_interpreters`), so this
#: tuple is the static floor, not the full set.
BACKENDS = ("auto", "jax", "pallas")

#: Environment default for ``compile_program(plan_cache_dir=...)``.
PLAN_CACHE_DIR_ENV = "REPRO_PLAN_CACHE_DIR"

_CACHE: dict = {}
_PLAN_CACHE: "OrderedDict" = OrderedDict()
_PLAN_CACHE_CAP = 128

# Split (multi-nest) schedules that measured faster on the stencil
# executor than on the JAX backend (real-TPU interpret=False runs).
# ``backend="auto"`` routes these programs to Pallas by name; everything
# else multi-nest keeps the JAX backend, whose XLA fusion already covers
# split schedules well.
PALLAS_SPLIT_WINS: set[str] = set()


def register_pallas_split_win(name: str) -> None:
    """Record that the named program's *split* schedule measured faster
    on the stencil executor, so ``backend="auto"`` routes it to Pallas.

    Call this from benchmark/deployment code after timing with
    ``interpret=False`` on a TPU runtime.  The table is keyed by
    program *name* (the operator's identity contract), so the default
    name is rejected — it would reroute every anonymously-built
    program.  Cached ``backend="auto"`` compilations of the program are
    invalidated so the new routing takes effect on the next
    :func:`compile_program` call."""
    if name == "program":
        raise ValueError(
            "refusing to register the default program name 'program' as a "
            "split win: give the program an explicit name"
        )
    PALLAS_SPLIT_WINS.add(name)
    for key in [k for k in _CACHE if k[1] == "auto" and k[0][0] == name]:
        del _CACHE[key]


def program_signature(program: Program):
    """A hashable identity for a program: two structurally identical
    programs (same rules/axioms/goals/loop order, same kernel callables
    — rebuilt lambdas compare by code object, see
    :func:`repro.core.plan.fn_key`) share compiled artifacts."""

    def params(ps):
        return tuple((p.name, str(p.pattern)) for p in ps)

    def exts(e):
        return tuple(sorted((d, x.size, x.lo, x.hi) for d, x in e.items()))

    rules = tuple(
        (r.name, params(r.inputs), params(r.outputs), r.kind, r.init,
         _fn_key(r.fn))
        for r in program.rules
    )
    axioms = tuple((str(a.term), exts(a.extents)) for a in program.axioms)
    goals = tuple((str(g.term), g.store_as, exts(g.extents))
                  for g in program.goals)
    return (program.name, rules, axioms, goals,
            tuple(program.loop_order), tuple(program.aliases))


def clear_compile_cache() -> None:
    """Drop every memoized compilation (all backends, both levels)."""
    _CACHE.clear()
    _PLAN_CACHE.clear()


def compile_cache_size() -> int:
    """Number of live entries in the signature-level compile cache."""
    return len(_CACHE)


def plan_cache_size() -> int:
    """Number of live entries in the plan-level (Pallas) compile cache."""
    return len(_PLAN_CACHE)


def plan_cache_cap() -> int:
    """Current LRU bound of the in-memory plan-level compile cache."""
    return _PLAN_CACHE_CAP


def set_plan_cache_cap(cap: int) -> int:
    """Re-bound the in-memory plan-level compile cache (LRU).

    Every compiled-interpreter entry pins its plan and closures, so the
    cache must not grow without bound in long-lived serving processes.
    Lowering the cap evicts least-recently-used entries immediately;
    returns the previous cap so callers can restore it."""
    global _PLAN_CACHE_CAP
    if cap < 1:
        raise ValueError(f"plan cache cap must be >= 1, got {cap}")
    prev, _PLAN_CACHE_CAP = _PLAN_CACHE_CAP, int(cap)
    while len(_PLAN_CACHE) > _PLAN_CACHE_CAP:
        _PLAN_CACHE.popitem(last=False)
    return prev


def _build_plan(program: Program):
    with span("hfav.infer"):
        idag = infer(program)
    with span("hfav.dataflow"):
        dag = build_dataflow(idag)
    with span("hfav.fusion"):
        schedule = fuse_inest_dag(dag)
    with span("hfav.storage"):
        plan = analyze_storage(schedule)
    return idag, plan


def pallas_auto_viable(plan: StoragePlan) -> bool:
    """Whether ``backend="auto"`` should offer this plan to the stencil
    interpreter.

    Single-nest schedules over a >= 2-dim loop order always qualify —
    the interpreter now covers rolling/row contraction, reductions
    (carried, kept-prefix and row-kept accumulators), outer grids,
    outer-dim halo reads via plane windows (streamed *and* same-nest
    produced variables), and cross-row materialized reads, and shapes
    the planner still rejects fail the probe with
    :class:`PallasUnsupported` and fall back to JAX.  Multi-nest (split)
    schedules qualify only when the program is a registered measured win
    (:func:`register_pallas_split_win`)."""
    if len(plan.schedule.program.loop_order) < 2:
        return False
    if len(plan.schedule.nests) == 1:
        return True
    return plan.schedule.program.name in PALLAS_SPLIT_WINS


def _run_plancheck(kplan: KernelPlan, mode: str, *, dtype,
                   dim_sizes=None) -> None:
    """Gate a plan on the static analyzer (:mod:`repro.core.plancheck`)
    per the resolved ``check_plans`` mode: ``"error"`` raises
    :class:`~repro.core.plancheck.PlanCheckError` on error-severity
    findings, ``"warn"`` turns every finding into a
    :class:`~repro.core.plancheck.PlanCheckWarning`, ``"off"`` skips
    the analyses entirely.  ``dim_sizes`` (``{size symbol: int}``)
    additionally enables the VMEM budget check."""
    if mode == "off":
        return
    with span("hfav.plancheck"):
        diags = check_plan(kplan,
                           sizes=dict(dim_sizes) if dim_sizes else None,
                           dtype_bytes=jnp.dtype(dtype).itemsize,
                           validate=False)
    if not diags:
        return
    if mode == "error" and has_errors(diags):
        raise PlanCheckError(
            f"plan {kplan.program!r} failed static analysis:\n" +
            "\n".join(f"  {d}" for d in diags), diags)
    for d in diags:
        warnings.warn(str(d), PlanCheckWarning, stacklevel=3)


def _emit_plan(kplan: KernelPlan, plan: Optional[StoragePlan],
               program: Optional[Program] = None, *, interpreter, dtype,
               interpret, use_cache=True, check="warn", dim_sizes=None,
               apply_mode="off", permuted=False) -> PallasGenerated:
    """Build (or fetch) the named registered interpreter for a finished
    kernel plan.

    Memoized on the interpreter name, :meth:`KernelPlan.cache_key` and
    the execution flags the interpreter declares it honors (un-honored
    flags are normalized out; LRU-bounded,
    :func:`set_plan_cache_cap`), so programs lowering to structurally
    equal plans share one compiled executor per interpreter — whether
    the plan came from the planner or from the on-disk cache — and two
    interpreters executing the same plan never collide.  Static
    analysis (``check``, a resolved ``check_plans`` mode) runs at build
    time, covering both the fresh-plan and disk-restored paths; a
    plan-cache hit is a plan that already passed.

    ``apply_mode`` (a resolved ``apply_layout`` mode) runs the
    LayoutApply pass (:mod:`repro.core.layoutapply`) over the plan
    first — only for layout-aware interpreters, and only when not
    ``"off"``.  The transformed plan's ``applied_layout`` record makes
    its :meth:`~KernelPlan.cache_key` distinct, so transformed and
    untransformed builds never share a plan-cache entry; the original
    plan is kept on the artifact (``.base_plan``) so the on-disk cache
    always persists the *untransformed* form (the pass re-runs per
    compilation, keeping cached plans mode-agnostic).

    Given the ``program`` the plan was made from, the executor runs each
    call in the loop order the device keeps the call's inputs in
    (:func:`repro.core.dimorder.device_ordered`): the program permuted
    to that order is planned and built on first need
    (:func:`_emit_permuted`), through this function with
    ``permuted=True``, which the plan-level cache key holds."""
    spec = get_interpreter(interpreter)
    base_plan = kplan
    layout_result = None
    if apply_mode != "off" and spec.layout_aware:
        layout_result = run_layout_pass(
            kplan, mode=apply_mode,
            sizes=dict(dim_sizes) if dim_sizes else None)
        kplan = layout_result.plan
    pkey = (interpreter, kplan.cache_key(), jnp.dtype(dtype).name,
            bool(interpret) and "interpret" in spec.flags, permuted)
    if use_cache:
        hit = _PLAN_CACHE.get(pkey)
        count("hfav.plan_cache.hit" if hit is not None
              else "hfav.plan_cache.miss")
        if hit is not None:
            _PLAN_CACHE.move_to_end(pkey)
            if hit.plan is None and plan is not None:
                # a disk-restored entry lacks the analysis-side
                # StoragePlan; this caller just built one — upgrade the
                # shared artifact so .schedule works everywhere
                hit.plan = plan
            return hit
    _run_plancheck(kplan, check, dtype=dtype, dim_sizes=dim_sizes)
    # the shared host half resolves the interpreter's build_call through
    # the registry (and runs the capability check, raising the typed
    # PlanUnsupported for plans outside the declared feature set)
    from .interpreters import execute_plan
    with span("hfav.emit"):
        fn = execute_plan(kplan, interpreter=interpreter, dtype=dtype,
                          interpret=interpret, permuted=permuted)
        if program is not None and not permuted:
            build = functools.partial(
                _emit_permuted, program, interpreter=interpreter,
                dtype=dtype, interpret=interpret, use_cache=use_cache,
                check=check, dim_sizes=dim_sizes, apply_mode=apply_mode)
            fn = device_ordered(program, kplan, fn, build, dtype=dtype)
        gen = PallasGenerated(kplan, fn, plan, interpreter=interpreter)
    gen.base_plan = base_plan
    gen.layout_result = layout_result
    if use_cache:
        _PLAN_CACHE[pkey] = gen
        while len(_PLAN_CACHE) > _PLAN_CACHE_CAP:
            _PLAN_CACHE.popitem(last=False)
    return gen


def _emit_permuted(program: Program, order: tuple[str, ...], **emit):
    """The ``(plan, host half)`` of ``program`` permuted to the loop
    order ``order`` (:func:`repro.core.dimorder.permute_program`),
    planned and built through :func:`_emit_plan` with ``emit``'s flags;
    ``None`` where the pipeline or the planner refuses it."""
    try:
        pprog = permute_program(program, order)
        idag, plan = _build_plan(pprog)
        with span("hfav.plan_pallas"):
            kplan = plan_pallas(plan, idag)
        gen = _emit_plan(kplan, plan, pprog, permuted=True, **emit)
    except (ValueError, InferenceError, Unfusable, PallasUnsupported,
            PlanCheckError):
        return None
    return gen.kernel_plan, gen.fn


def _emit_pallas(plan, idag, *, interpreter, dtype, interpret,
                 use_cache=True, check="warn",
                 dim_sizes=None, apply_mode="off") -> PallasGenerated:
    """Plan, then interpret — through the plan-level cache.

    The planner runs unconditionally (it is cheap and raises
    :class:`PallasUnsupported` for unsupported shapes); interpreter
    construction is memoized by :func:`_emit_plan`."""
    with span("hfav.plan_pallas"):
        kplan = plan_pallas(plan, idag)
    return _emit_plan(kplan, plan, plan.schedule.program,
                      interpreter=interpreter, dtype=dtype,
                      interpret=interpret, use_cache=use_cache, check=check,
                      dim_sizes=dim_sizes, apply_mode=apply_mode)


def _load_plan_from_disk(program: Program, backend: str,
                         plan_cache_dir) -> Optional[KernelPlan]:
    """L2 lookup: fetch the program's serialized plan, honoring auto's
    routing rules (a pre-warmed multi-nest plan must not flip an
    ``auto`` compilation that would otherwise take the JAX backend —
    split schedules still require a registered win)."""
    from .plancache import PlanCache, program_plan_key
    try:
        with span("hfav.plan_disk", op="get"):
            kplan = PlanCache(plan_cache_dir).get(program_plan_key(program))
    except OSError:  # uncreatable/unreadable cache dir: cold compile
        return None
    if kplan is None:
        return None
    count("hfav.plan_disk.hit")
    if backend == "auto" and len(kplan.calls) != 1 \
            and program.name not in PALLAS_SPLIT_WINS:
        return None
    return kplan


def _store_plan_to_disk(program: Program, kplan: KernelPlan,
                        plan_cache_dir, only_if_missing: bool = False) -> None:
    """L2 fill: persist a planned program (best-effort — plans whose
    callables have no stable spec, and filesystem failures, are
    skipped, not errors).  ``only_if_missing`` makes the fill
    idempotent for hot paths that revisit the same program."""
    from .plancache import PlanCache, program_plan_key
    try:
        with span("hfav.plan_disk", op="put"):
            cache = PlanCache(plan_cache_dir)
            key = program_plan_key(program)
            if only_if_missing and cache.has(key):
                return
            cache.put(key, kplan)
    except OSError:
        pass


def _pallas_auto_probe(plan, idag, *, dtype, interpret,
                       use_cache=True, check="warn", dim_sizes=None,
                       apply_mode="off"):
    """The single auto-routing probe shared by :func:`compile_program`
    and :func:`explain`: build the Pallas execution if the plan is
    viable, return None (fall back to JAX) if it is not, the planner
    raises :class:`PallasUnsupported`, the static analyzer rejects the
    plan under ``check="error"``, or — when concrete ``dim_sizes`` are
    known — the estimated resident VMEM exceeds the budget
    (``REPRO_VMEM_BUDGET_BYTES``) or the vectorization model rejects
    the shape (:func:`repro.core.vecscan.auto_vec_reject`: lane
    occupancy under ``REPRO_VEC_MIN_OCCUPANCY``, redundant-load ratio
    over the opt-in ``REPRO_VEC_AUTO_MAX_RATIO``): a nest that cannot
    hold its windows in VMEM, or that wastes most of every padded
    lane, is better served by XLA than by a thrashing stencil
    pipeline."""
    if not pallas_auto_viable(plan):
        return None
    try:
        with span("hfav.plan_pallas"):
            kplan = plan_pallas(plan, idag)
    except PallasUnsupported:
        return None
    if dim_sizes:
        with span("hfav.autoprobe"):
            est = vmem_bytes(kplan, dict(dim_sizes),
                             dtype_bytes=jnp.dtype(dtype).itemsize)
            if est > vmem_budget(None):
                return None
            if auto_vec_reject(kplan, dict(dim_sizes),
                               dtype_bytes=jnp.dtype(dtype).itemsize):
                return None
    try:
        return _emit_plan(kplan, plan, plan.schedule.program,
                          interpreter="pallas", dtype=dtype,
                          interpret=interpret, use_cache=use_cache,
                          check=check, dim_sizes=dim_sizes,
                          apply_mode=apply_mode)
    except PlanCheckError:
        return None


def _attach_vec_report(gen, want: bool, dim_sizes, dtype):
    """Annotate a plan-backed artifact with its
    :class:`~repro.core.vecscan.VecReport` when the compilation asked
    for one.  A no-op for the legacy JAX emitter (no kernel plan
    exists); recomputed per request so a later call carrying concrete
    ``dim_sizes`` upgrades a cached artifact's symbolic report."""
    if want and isinstance(gen, PallasGenerated):
        gen.vec_report = scan_plan(
            gen.kernel_plan,
            sizes=dict(dim_sizes) if dim_sizes else None,
            dtype_bytes=jnp.dtype(dtype).itemsize)
    return gen


def compile_program(
    program: Program,
    backend: str = "auto",
    *,
    dtype=jnp.float32,
    interpret: Optional[bool] = None,
    use_cache: bool = True,
    plan_cache_dir=None,
    check_plans: Optional[str] = None,
    dim_sizes=None,
    vec_report: bool = False,
    apply_layout: Optional[str] = None,
) -> Union[Generated, PallasGenerated]:
    """Compile ``program`` through the HFAV pipeline onto a backend.

    ``interpret`` only affects the Pallas backend (CPU validation vs
    TPU execution).  ``interpret=None`` resolves to
    interpret mode exactly where the default backend is not a TPU
    (:func:`repro.core.interpreters.resolve_interpret`), and the cache
    keys hold the resolved value.  Results are memoized; pass
    ``use_cache=False`` to force a rebuild.

    ``plan_cache_dir`` names a durable on-disk plan cache
    (:mod:`repro.core.plancache`): Pallas-bound compilations first try
    to load the program's serialized :class:`KernelPlan` from there —
    a hit skips the entire analysis pipeline (inference, fusion,
    storage, planning; the loaded plan is re-validated via
    :meth:`KernelPlan.validate`) — and freshly-planned programs are
    persisted back, so a second process compiles warm.  Pre-populate
    with ``scripts/warm_cache.py``; ``use_cache`` governs only the
    in-memory caches.  When ``plan_cache_dir`` is omitted the
    ``REPRO_PLAN_CACHE_DIR`` environment variable supplies the default.

    ``check_plans`` gates every Pallas-bound plan on the static
    analyzer (:mod:`repro.core.plancheck`): ``"warn"`` (the default,
    overridable via ``REPRO_CHECK_PLANS``) reports findings as
    :class:`~repro.core.plancheck.PlanCheckWarning`, ``"error"`` raises
    :class:`~repro.core.plancheck.PlanCheckError` on error-severity
    findings (``backend="auto"`` falls back to JAX instead), ``"off"``
    skips analysis.  Plans are analyzed when built; in-memory cache
    hits return the already-vetted artifact without re-linting.

    ``dim_sizes`` (``{size symbol: int}``, e.g. ``{"Nj": 512}``)
    declares the intended problem size: it enables the VMEM budget
    diagnostic (PC003), lets ``backend="auto"`` route nests whose
    estimated resident footprint exceeds ``REPRO_VMEM_BUDGET_BYTES``
    (default ~16 MiB) to the JAX backend, and arms the vectorization
    tiebreaker (:func:`repro.core.vecscan.auto_vec_reject`).

    ``vec_report=True`` attaches the vectorization analyzer's
    :class:`~repro.core.vecscan.VecReport`
    (:func:`repro.core.vecscan.scan_plan`, concrete when ``dim_sizes``
    is given) to the returned artifact's ``.vec_report`` — plan-backed
    backends only; the legacy JAX emitter has no kernel plan to
    analyze.

    ``apply_layout`` (``"off"``/``"auto"``/``"force"``; ``None``
    defers to ``REPRO_APPLY_LAYOUT``, defaulting to ``"off"``) gates
    the LayoutApply transformation pass
    (:mod:`repro.core.layoutapply`): when the target interpreter is
    layout-aware, VecScan's serialized hints are realized on the plan
    before it builds — ``"auto"`` keeps the transform only when the
    re-run analyzer's predicted redundant-load ratio drops, ``"force"``
    applies every handled hint kind (including the non-bit-exact
    ones).  The resolved mode participates in the compile cache key,
    and the plan-level cache distinguishes the plans themselves
    (``applied_layout`` is structural), so modes never share entries;
    the on-disk plan cache always stores the untransformed plan.

    The call runs under the span ``hfav.compile_program`` and each pass
    under a child span, and the caches count their hits and misses
    (:mod:`repro.trace`; docs/ARCHITECTURE.md, "Tracing")."""
    with span("hfav.compile_program", program=program.name,
              backend=backend):
        if backend in ("auto", "jax"):
            spec = None
        else:
            try:
                spec = get_interpreter(backend)
            except ValueError:
                raise ValueError(
                    f"unknown backend {backend!r}; expected 'auto', 'jax' or a "
                    f"registered interpreter: {registered_interpreters()}"
                ) from None
        interpret = resolve_interpret(interpret)
        check = resolve_check_mode(check_plans)
        apply_mode = resolve_apply_mode(apply_layout)
        if plan_cache_dir is None:
            plan_cache_dir = os.environ.get(PLAN_CACHE_DIR_ENV) or None
        sizes_key = tuple(sorted(dim_sizes.items())) if dim_sizes else None
        # flags an interpreter does not honor are normalized out of the key
        # (a pure-JAX interpreter compiles identically either way), and
        # apply_layout normalizes to "off" for layout-oblivious backends,
        # where the pass never runs
        key = (program_signature(program), backend, jnp.dtype(dtype).name,
               bool(interpret) and (spec is None or "interpret" in spec.flags),
               sizes_key,
               apply_mode if spec is not None and spec.layout_aware
               else "off")
        if use_cache:
            hit = _CACHE.get(key)
            count("hfav.compile_cache.hit" if hit is not None
                  else "hfav.compile_cache.miss")
            if hit is not None:
                if plan_cache_dir is not None and isinstance(hit,
                                                             PallasGenerated):
                    # the program compiled before this call named a cache
                    # dir: back-fill the L2 so the next process runs warm
                    # (always the untransformed plan — LayoutApply re-runs
                    # per compilation, so cached plans stay mode-agnostic)
                    _store_plan_to_disk(
                        program,
                        getattr(hit, "base_plan", None) or hit.kernel_plan,
                        plan_cache_dir, only_if_missing=True)
                return _attach_vec_report(hit, vec_report, dim_sizes, dtype)
        if plan_cache_dir is not None and backend != "jax":
            # disk-restored artifacts carry no StoragePlan, so they live
            # under a marked key: a later compile *without* plan_cache_dir
            # must rebuild the full artifact, not inherit the degraded one
            dkey = key + ("disk",)
            if use_cache:
                hit = _CACHE.get(dkey)
                if hit is not None:
                    return _attach_vec_report(hit, vec_report, dim_sizes,
                                              dtype)
            kplan = _load_plan_from_disk(program, backend, plan_cache_dir)
            if kplan is not None:
                gen = _emit_plan(kplan, None, program,
                                 interpreter="pallas" if backend == "auto"
                                 else backend,
                                 dtype=dtype, interpret=interpret,
                                 use_cache=use_cache, check=check,
                                 dim_sizes=dim_sizes, apply_mode=apply_mode)
                if use_cache:
                    _CACHE[dkey] = gen
                return _attach_vec_report(gen, vec_report, dim_sizes, dtype)
        idag, plan = _build_plan(program)
        if backend == "jax":
            with span("hfav.emit"):
                gen: Union[Generated, PallasGenerated] = generate(plan, idag)
        elif backend == "auto":
            gen = _pallas_auto_probe(plan, idag, dtype=dtype, interpret=interpret,
                                     use_cache=use_cache, check=check,
                                     dim_sizes=dim_sizes, apply_mode=apply_mode)
            if gen is None:
                with span("hfav.emit"):
                    gen = generate(plan, idag)
        else:
            gen = _emit_pallas(plan, idag, interpreter=backend, dtype=dtype,
                               interpret=interpret,
                               use_cache=use_cache, check=check,
                               dim_sizes=dim_sizes, apply_mode=apply_mode)
        if plan_cache_dir is not None and isinstance(gen, PallasGenerated):
            _store_plan_to_disk(
                program, getattr(gen, "base_plan", None) or gen.kernel_plan,
                plan_cache_dir)
        if use_cache:
            _CACHE[key] = gen
        return _attach_vec_report(gen, vec_report, dim_sizes, dtype)


class BatchedGenerated:
    """A compiled program vmapped over a leading batch axis.

    Wraps the single-example artifact (``.gen``, a :class:`Generated`
    or :class:`PallasGenerated` from :func:`compile_program`) with a
    batched callable: ``fn(arrays)`` takes a dict of input arrays each
    carrying one extra *leading* batch axis (the same batch width on
    every input) and returns the per-store output dict with the same
    leading axis — bit-identical to running ``gen.fn(**example)`` per
    batch element and stacking (vmap of a deterministic elementwise/
    stencil computation commutes with per-example execution).  Built by
    :func:`compile_batched`; the serving engine
    (:mod:`repro.serve.plans`) executes every micro-batch through one
    of these."""

    def __init__(self, gen, fn, *, backend: str, jitted: bool):
        self.gen = gen
        self.fn = fn
        self.backend = backend
        self.jitted = jitted

    def __repr__(self):
        return (f"BatchedGenerated(backend={self.backend!r}, "
                f"jitted={self.jitted}, gen={self.gen!r})")


def compile_batched(
    program: Program,
    backend: str = "auto",
    *,
    jit: bool = True,
    **kwargs,
) -> BatchedGenerated:
    """Compile ``program`` and vmap the result over a leading batch axis.

    The single-example compilation goes through :func:`compile_program`
    (all of its keyword flags — ``dtype``, ``interpret``,
    ``plan_cache_dir``, ``dim_sizes``, … — pass through unchanged, so
    the disk plan cache and the in-memory caches behave exactly as for
    unbatched compiles).  The returned :class:`BatchedGenerated`'s
    ``fn`` maps a dict of inputs with a shared leading batch axis to
    the stacked per-store outputs; with ``jit=True`` (the default) the
    vmapped computation is additionally ``jax.jit``-ed, so each
    distinct batch shape traces once and replays compiled thereafter —
    the property the serving engine's shape buckets exist to exploit.

    Every registered plan interpreter and the legacy ``"jax"`` emitter
    produce traceable executors, so all backends are vmap-safe (pinned
    by the cross-backend conformance tests; see the vmap note in
    docs/BACKENDS.md)."""
    gen = compile_program(program, backend, **kwargs)

    def _one(arrays):
        return gen.fn(**arrays)

    fn = jax.vmap(_one)
    if jit:
        fn = jax.jit(fn)
    return BatchedGenerated(gen, fn, backend=backend, jitted=jit)


def explain(program: Program, *, dtype=jnp.float32,
            interpret: Optional[bool] = None, verbose: bool = False,
            dim_sizes=None, apply_layout: Optional[str] = None) -> str:
    """Human-readable transformation report (the paper's debugging output).

    The keyword flags mirror :func:`compile_program` and feed the same
    shared probe (:func:`_pallas_auto_probe`), so the reported
    ``auto backend`` is exactly what ``backend="auto"`` would pick for a
    compilation with those flags — including split-win routing,
    a non-default ``dtype``, and (when ``dim_sizes`` is given) the
    VMEM-budget consult.

    ``verbose=True`` appends the rendered
    :class:`~repro.core.plan.KernelPlan` (grid ranges, window and
    accumulator plans, per-step reads/writes, output trim rules) when
    the probe lowered one — the declarative contract the interpreter
    will execute — followed by the estimated resident-VMEM footprint:
    symbolic per-buffer formulas always, concrete per-nest byte totals
    when ``dim_sizes`` (``{size symbol: int}``) resolves them — and
    the vectorization analysis
    (:func:`repro.core.vecscan.scan_plan`: access-class counts,
    redundant-load ratio, window reuse distances, PV diagnostics and
    layout hints) — followed by the LayoutApply report
    (:func:`repro.core.layoutapply.apply_layout` run in the resolved
    ``apply_layout`` mode, same contract as
    :func:`compile_program`): which hints the pass applied, which it
    skipped and why, which stay advisory, and the predicted
    redundant-load ratio before and after."""
    idag, plan = _build_plan(program)
    schedule = plan.schedule
    dag = schedule.dag
    gen = _pallas_auto_probe(plan, idag, dtype=dtype,
                             interpret=resolve_interpret(interpret),
                             dim_sizes=dim_sizes)
    backend = "pallas" if gen is not None else "jax"
    lines = [
        f"program: {program.name}",
        f"raps: {len(idag.raps)}  groups: {len(dag.groups)}  "
        f"fused nests: {schedule.n_toplevel()}",
        f"auto backend: {backend}",
        "--- fused schedule ---",
        schedule.pretty(),
        "--- storage plan ---",
        plan.summary(),
    ]
    if verbose:
        lines.append("--- kernel plan ---")
        if gen is not None:
            lines.append(gen.kernel_plan.render())
            itemsize = jnp.dtype(dtype).itemsize
            lines.append("--- vmem estimate ---")
            lines.extend(render_vmem(gen.kernel_plan, dtype_bytes=itemsize))
            if dim_sizes:
                rep = vmem_report(gen.kernel_plan, dict(dim_sizes),
                                  dtype_bytes=itemsize)
                for nest, r in rep.items():
                    lines.append(
                        f"  {nest}: {r['total']} B resident "
                        f"(budget {vmem_budget(None)} B)")
            lines.append("--- vectorization ---")
            vrep = scan_plan(gen.kernel_plan,
                             sizes=dict(dim_sizes) if dim_sizes else None,
                             dtype_bytes=itemsize)
            lines.extend(vrep.render())
            lines.append("--- layout apply ---")
            mode = resolve_apply_mode(apply_layout)
            lres = run_layout_pass(
                gen.kernel_plan, mode=mode,
                sizes=dict(dim_sizes) if dim_sizes else None)
            lines.extend(render_apply(lres, mode))
        else:
            lines.append("(auto picked the JAX backend: no stencil plan)")
    return "\n".join(lines)
