"""Pallas TPU interpreter for HFAV :class:`~repro.core.plan.KernelPlan` IR.

This is the TPU-native realization of the paper's generated code
(Section 3.6 + the hardware adaptation of DESIGN.md §2), now a pure
**interpreter**: it consumes the declarative plan produced by
:func:`repro.core.codegen_pallas.plan_pallas` and contains no analysis
logic of its own — every grid range, window shape, lead and trim rule
arrives pre-computed in the plan.  The fused iteration nest's steady
state becomes the Pallas grid, and *all* rolling buffers — including the
optional input-row window the paper mentions for COSMO — live in VMEM
scratch that persists across sequential grid steps.

The grid is ``(*outer, steps_j)``: the plan's outer :class:`GridDim`
entries map one-to-one onto leading grid dimensions and the row dim onto
the last, each covering its canonical range ``[lo, N_d + hi_off)`` —
narrowed by halo'd goals and extended downward by plane-window warm-up
tiles.  TPU grids execute sequentially with the last dimension fastest,
which is exactly the fused nest's traversal order — VMEM scratch
therefore carries state both across rows *and* across outer-tile
boundaries.  Each grid step:

1. takes exactly one new row per array input into that input's VMEM
   window, ``lead`` rows ahead of the canonical point.  Rows arrive from
   HBM in 8-row (sublane-tile) groups that consecutive grid steps
   revisit — Mosaic refuses one-row blocks — either through the
   BlockSpec index map, or, with ``double_buffer=True``, through an
   explicitly double-buffered ``make_async_copy`` pair that prefetches
   the next group while the current rows are being consumed.  Inputs
   read at non-zero offsets in the *plane dim* (the outer identifier
   adjacent to the row dim — ``u[k-1][j][i]`` stencils) use a
   *multi-plane window* instead of a rolling row window:
   ``(p_stages, rows, width)`` VMEM where whole planes stay resident across outer tiles and the streamed row lands in
   the newest plane, ``p_lead`` tiles ahead (Fig. 9a/9b applied one loop
   level further out);
2. executes every fused step at its software-pipeline lead, reading
   neighbor rows from VMEM windows via mod-``stages`` index arithmetic
   (the functional form of the paper's pointer rotation, Fig. 9a/9b) —
   and neighbor *planes* via mod-``p_stages`` plane slots.  Variables
   *produced in the nest* and read at plane offsets write a **producer
   plane window** (:class:`~repro.core.plan.WindowPlan` in plane mode):
   the producing step runs ``p_lead`` tiles ahead in the plane dim and
   seats each row at its absolute plane-row index (store predicated to
   the plane's row extent), so ``v[k-1][j][i]``-style consumers read
   older resident planes without a round-trip through HBM.  Reduction
   steps combine into VMEM accumulator rows carried across grid steps
   (the vector partial accumulators of Section 3.5), predicated on the
   canonical point being inside the reduced extent (rows *and* outer
   tiles) — carried across the whole grid or re-initialized per
   kept-prefix tile (:attr:`~repro.core.plan.AccPlan.n_kept`); row-kept
   reductions carry nothing and emit one identity-padded partial row per
   step instead;
3. writes one row per terminal output into its revisited 8-row output
   block (the fill, then the value at its static lane offset), which
   goes back to HBM when the grid moves to the next block; accumulator
   outputs are dumped into a revisited block whose final grid step
   (per kept tile) holds the fully-combined partial-accumulator row.

Rolling windows are padded to the 128-wide TPU lane tile (the
vector-length expansion of Fig. 9c).  Warm-up/drain grid steps compute
garbage rows into padded outputs that :func:`execute_plan`'s host layer
slices away — the masked steady-state ('HFAV + Tuning') form.

All row widths in the plan are stored as *deltas against Ni* (and row
counts as deltas against Nj) so one plan serves every problem size; they
are concretized in :func:`build_call`.

:func:`execute_plan` is the host half of the interpreter: it resolves
runtime sizes through the plan's :class:`~repro.core.plan.AxiomPlan`
shape contracts, threads the environment between stencil calls and host
steps, and assembles each padded device output back to its canonical
array (trim warm-up rows/tiles, re-seat goal origins, lane-reduce folded
accumulators) — exactly as the plan's :class:`OutputPlan` trim/seat
rules dictate.
"""
from __future__ import annotations

import functools
import operator
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.interpreters import (InterpreterSpec, register_interpreter,
                                  require_hazard_free, require_linked_fns)
from ...core.plan import (PLAN_FEATURES, CallPlan, KernelPlan,
                          PallasUnsupported, WindowPlan)
from ...core.plancheck import VMEM_CAPACITY, call_vmem, scoped_vmem_limit

LANE = 128
#: Rows per streamed block: one f32 sublane tile.  Mosaic accepts a
#: block only when its last two dims are multiples of (8, 128) or equal
#: to the array's, so rows move between HBM and VMEM in 8-row groups
#: that consecutive grid steps revisit.
SUBLANE = 8


def _pad_to_lane(w: int) -> int:
    return max(LANE, ((w + LANE - 1) // LANE) * LANE)


def _mod(pos, stages: int):
    """Floor-mod robust to negative pipeline-priming positions."""
    return jax.lax.rem(jax.lax.rem(pos, stages) + stages, stages)


def build_call(call: CallPlan, sizes: tuple[int, ...], dtype,
               interpret: bool = False, double_buffer: bool = False):
    """Concretize one :class:`CallPlan` for a problem size and build the
    pallas_call.

    ``sizes`` is ``(*outer_sizes, Nj, Ni)`` with ``call.n_outer`` leading
    outer extents (``(Nj, Ni)`` for a plain 2-D nest).  Returns
    ``(fn, steps_j)``; the call maps the input arrays to one padded
    output per ``call.outputs`` entry (a list when there are several).
    Row outputs are ``(*grid, rows, ni)`` with ``rows`` the grid's
    ``steps_j`` rounded up to a whole sublane tile; row ``t`` holds
    iteration position ``t + x_lo + out.lead``.  Carried-accumulator
    outputs are ``(1, width)`` and kept-prefix accumulator outputs
    ``(*kept grid, 1, width)``.

    Each grid step computes one row.  Array inputs and row outputs move
    in 8-row blocks that consecutive grid steps revisit: the step reads
    (or writes) its row at ``row % 8`` inside the block.
    ``double_buffer=True`` replaces the BlockSpec input streaming with an
    explicit two-slot async-DMA pipeline: array inputs stay in HBM
    (``memory_space=ANY``); whenever the next grid step needs a new
    8-row group, the current step starts its copy into the other slot,
    so the input DMA overlaps the compute of the current rows.

    The VMEM the call needs (:func:`repro.core.plancheck.call_vmem`) is
    passed on as the compiler's scoped VMEM limit when it exceeds the
    default; a call that cannot fit the core's VMEM is refused with
    :class:`~repro.core.plan.PallasUnsupported`."""
    n_out = call.n_outer
    if len(sizes) != n_out + 2:
        raise ValueError(
            f"call {call.name} has n_outer={n_out} but got sizes {sizes}"
        )
    require_linked_fns(call)
    require_hazard_free(call)
    *outer_sizes, nj, ni = sizes
    o_lo = call.outer_lo
    o_hi = call.outer_hi_off
    gsz = [outer_sizes[d] + o_hi[d] - o_lo[d] for d in range(n_out)]
    steps_j = (nj + call.x_hi_off) - call.x_lo
    out_rows = pl.cdiv(steps_j, SUBLANE) * SUBLANE
    total_steps = steps_j
    for s in gsz:
        total_steps *= s

    arr_ins = [i for i in call.inputs if not i.scalar]
    row_ins = [i for i in arr_ins if not i.plane]
    plane_ins = [i for i in arr_ins if i.plane]
    roll_wins = [WindowPlan(f"in_{i.name}", i.stages, i.i_lo, i.i_hi)
                 for i in row_ins] + [w for w in call.windows if not w.plane]
    plane_wins = [w for w in call.windows if w.plane]
    bwidth = {w.name: ni + (w.i_hi - w.i_lo) for w in roll_wins + plane_wins}
    win_h = {w.name: nj + (w.j_hi - w.j_lo) for w in plane_wins}
    acc_w = {a.name: ni + a.w_off for a in call.accs}
    ref_idx = {ispec.name: k for k, ispec in enumerate(call.inputs)}
    ispec_of = {i.name: i for i in arr_ins}
    in_h = {i.name: nj + (i.j_hi - i.j_lo) for i in arr_ins}
    in_w = {i.name: ni + (i.i_hi - i.i_lo) for i in arr_ins}
    # rows per streamed input block (the whole array when it is shorter)
    rb = {i.name: min(SUBLANE, in_h[i.name]) for i in arr_ins}
    # lanes per explicit DMA: Mosaic copies whole lane tiles, reaching
    # into the lane padding of the TPU's tiled HBM layout, which the
    # interpreter's arrays do not have
    dma_w = {n: w if interpret else _pad_to_lane(w) for n, w in in_w.items()}
    n_scratch = len(roll_wins) + len(plane_ins) + len(plane_wins) \
        + len(call.accs)

    def _row_pos(ispec, x):
        """Source row index of ``ispec`` for canonical position ``x``
        (clamped: edge rows repeat during warm-up/drain)."""
        return jnp.clip(x + ispec.lead - ispec.j_lo, 0, in_h[ispec.name] - 1)

    def _outer_src(ispec, pos):
        """Source indices for the input's own outer dims at canonical
        outer positions ``pos`` (one per grid outer dim).  The plane dim
        (last outer dim) of a plane-window input runs ``p_lead`` tiles
        ahead; all indices are clamped so warm-up/drain tiles fetch edge
        planes instead of faulting."""
        a_out = ispec.n_outer
        ilos = ispec.outer_los or (0,) * a_out
        ihis = ispec.outer_his or (0,) * a_out
        idxs = []
        for li, d in enumerate(range(n_out - a_out, n_out)):
            n_planes = outer_sizes[d] + ihis[li] - ilos[li]
            p = pos[d]
            if ispec.plane and d == n_out - 1:
                p = p + ispec.p_lead
            idxs.append(jnp.clip(p - ilos[li], 0, n_planes - 1))
        return idxs

    def _group(ispec, pos_outer, j_id):
        """Where ``ispec``'s row for one grid step sits in HBM: its outer
        source indices, the first row of its ``rb``-row group and the
        row's offset inside the group.  On the TPU groups start on a
        sublane tile, so the last one of an array whose rows are not a
        multiple of 8 reaches into the tile padding, as the BlockSpec
        pipeline's own ragged last block does; the interpreter, whose
        arrays have no padding, shifts that group up instead."""
        r = _row_pos(ispec, j_id + call.x_lo)
        n = rb[ispec.name]
        start = r - jax.lax.rem(r, n)
        if interpret:
            start = jnp.minimum(start, in_h[ispec.name] - n)
        else:
            start = pl.multiple_of(start, SUBLANE)
        return _outer_src(ispec, pos_outer), start, r - start

    def _decode(lin):
        """Canonical outer positions and row-grid index of linear grid
        step ``lin`` (TPU grids run with the last dimension fastest)."""
        j_id = jax.lax.rem(lin, steps_j)
        rest = jax.lax.div(lin, steps_j)
        pos = [None] * n_out
        for d in reversed(range(n_out)):
            pos[d] = jax.lax.rem(rest, gsz[d]) + o_lo[d]
            rest = jax.lax.div(rest, gsz[d])
        return pos, j_id

    def kernel(*refs):
        nin = len(call.inputs)
        in_refs = refs[:nin]
        o_refs = refs[nin:nin + len(call.outputs)]
        scratch = refs[nin + len(call.outputs):]
        ref_of = {w.name: (r, w) for r, w in zip(scratch, roll_wins)}
        plane_of = {i.name: r for i, r in
                    zip(plane_ins, scratch[len(roll_wins):])}
        pwin_of = {w.name: (r, w) for r, w in zip(
            scratch[len(roll_wins) + len(plane_ins):], plane_wins)}
        acc_of = {a.name: (r, a) for r, a in zip(
            scratch[len(roll_wins) + len(plane_ins) + len(plane_wins):],
            call.accs)}

        outer_ids = [pl.program_id(d) for d in range(n_out)]
        opos = [outer_ids[d] + o_lo[d] for d in range(n_out)]
        jid = pl.program_id(n_out)
        x = jid + call.x_lo

        def _store_window(ispec, row, pos_outer, xx):
            """Seat one freshly-streamed row: rolling row windows rotate
            by mod-``stages`` position arithmetic; plane windows place
            the row at its absolute array index inside the newest plane
            (``p_lead`` tiles ahead, mod-``p_stages`` plane slot)."""
            if ispec.plane:
                slot = _mod(pos_outer[n_out - 1] + ispec.p_lead,
                            ispec.p_stages)
                plane_of[ispec.name][
                    slot, _row_pos(ispec, xx),
                    pl.ds(0, in_w[ispec.name])] = row
            else:
                ref, w = ref_of[f"in_{ispec.name}"]
                ref[_mod(xx + ispec.lead, w.stages),
                    pl.ds(0, bwidth[w.name])] = row

        # 0. identity-initialize accumulators: carried accumulators
        # (n_kept == 0) once on the very first grid step, kept-prefix
        # accumulators at the first step of every kept tile.
        for a in call.accs:
            first = jid == 0
            for d in range(a.n_kept, n_out):
                first &= outer_ids[d] == 0

            @pl.when(first)
            def _init_acc(_a=a):
                r, _ = acc_of[_a.name]
                r[0, :] = jnp.full((r.shape[1],), _a.init, dtype)

        # 1. stream one new row per array input into its VMEM window
        if double_buffer and arr_ins:
            dma_stage = dict(zip(
                (i.name for i in arr_ins),
                scratch[n_scratch:n_scratch + len(arr_ins)]))
            dma_sems = scratch[n_scratch + len(arr_ins)]
            cur_slot = scratch[n_scratch + len(arr_ins) + 1]
            # Linear grid-step odometer: `lin` enumerates steps in
            # execution order; the neighbours decide whether the row
            # group changes at this step (wait) or at the next (prefetch).
            lin = jid
            mult = steps_j
            for d in reversed(range(n_out)):
                lin = lin + outer_ids[d] * mult
                mult *= gsz[d]
            prv = _decode(jnp.maximum(lin - 1, 0))
            nxt = _decode(jnp.minimum(lin + 1, total_steps - 1))

            def _copy(ai, ispec, group, to_slot):
                """The group DMA descriptor for one input (start and
                wait must agree on shape)."""
                idx, start, _ = group
                src_idx = tuple(pl.ds(i, 1) for i in idx)
                src_idx += (pl.ds(start, rb[ispec.name]),
                            pl.ds(0, dma_w[ispec.name]))
                return pltpu.make_async_copy(
                    in_refs[ref_idx[ispec.name]].at[src_idx],
                    dma_stage[ispec.name].at[to_slot],
                    dma_sems.at[ai, to_slot],
                )

            def _moved(g0, g1):
                """Whether two steps' row groups differ."""
                return functools.reduce(
                    operator.or_,
                    [a != b for a, b in zip(g0[0], g1[0])] + [g0[1] != g1[1]])

            for ai, ispec in enumerate(arr_ins):
                here = _group(ispec, opos, jid)
                fresh = (lin == 0) | _moved(here, _group(ispec, *prv))

                @pl.when(lin == 0)
                def _prime(_ai=ai, _sp=ispec, _g=here):
                    cur_slot[_ai] = 0
                    _copy(_ai, _sp, _g, 0).start()

                @pl.when((lin > 0) & fresh)
                def _flip(_ai=ai):
                    cur_slot[_ai] = 1 - cur_slot[_ai]

                slot = cur_slot[ai]

                @pl.when(fresh)
                def _wait(_ai=ai, _sp=ispec, _g=here, _s=slot):
                    _copy(_ai, _sp, _g, _s).wait()

                ahead = _group(ispec, *nxt)

                @pl.when((lin + 1 < total_steps) & _moved(ahead, here))
                def _prefetch(_ai=ai, _sp=ispec, _g=ahead, _s=slot):
                    _copy(_ai, _sp, _g, 1 - _s).start()

                row = dma_stage[ispec.name][
                    (slot,) + (0,) * ispec.n_outer
                    + (here[2], pl.ds(0, in_w[ispec.name]))]
                _store_window(ispec, row, opos, x)
        else:
            for ispec in arr_ins:
                src = in_refs[ref_idx[ispec.name]]
                r = jax.lax.rem(_row_pos(ispec, x), rb[ispec.name])
                row = src[(0,) * ispec.n_outer + (r, slice(None))]
                _store_window(ispec, row, opos, x)

        # 2. fused steps, in dataflow order, at their leads
        local: dict[str, jnp.ndarray] = {}
        for step in call.steps:
            ins = []
            cur = None
            if step.acc is not None:
                aref, _ = acc_of[step.acc]
                cur = aref[0, pl.ds(0, acc_w[step.acc])]
                ins.append(cur)
            for rd in step.reads:
                w = ni + rd.w_off
                if rd.src.startswith("local:"):
                    lrow = local[rd.src[6:]]
                    ins.append(jax.lax.slice(lrow, (rd.col0,), (rd.col0 + w,)))
                elif rd.src.startswith("scalar:"):
                    sref = in_refs[ref_idx[rd.src[7:]]]
                    ins.append(sref[0, 0])
                elif rd.src.startswith("in_") and \
                        ispec_of.get(rd.src[3:]) is not None and \
                        ispec_of[rd.src[3:]].plane:
                    # streamed plane-window read: plane slot by mod-stage
                    # rotation in the plane dim, absolute row inside it
                    ispec = ispec_of[rd.src[3:]]
                    slot = _mod(opos[n_out - 1] + rd.p_off, ispec.p_stages)
                    r_idx = jnp.clip(x + rd.j_off - ispec.j_lo, 0,
                                     in_h[ispec.name] - 1)
                    ins.append(plane_of[ispec.name][
                        slot, r_idx, pl.ds(rd.col0 - ispec.i_lo, w)])
                elif rd.src in pwin_of:
                    # producer plane-window read: older planes resident,
                    # rows addressed absolutely (clamped on warm-up)
                    pref, pw = pwin_of[rd.src]
                    slot = _mod(opos[n_out - 1] + rd.p_off, pw.p_stages)
                    r_idx = jnp.clip(x + rd.j_off - pw.j_lo, 0,
                                     win_h[pw.name] - 1)
                    ins.append(pref[slot, r_idx,
                                    pl.ds(rd.col0 - pw.i_lo, w)])
                else:
                    ref, b = ref_of[rd.src]
                    stage = _mod(x + rd.j_off, b.stages)
                    ins.append(ref[stage, pl.ds(rd.col0 - b.i_lo, w)])
            vals = call.fns[step.fn_idx](*ins)
            if step.acc is not None:
                # predicated combine: warm-up/drain rows *and* tiles
                # must not pollute
                lo, hi = step.valid
                pos = x + step.lead
                ok = (pos >= lo) & (pos < nj + hi)
                for d, (vlo, vhi) in enumerate(step.valid_outer):
                    ok &= (opos[d] >= vlo) & (opos[d] < outer_sizes[d] + vhi)
                aref, _ = acc_of[step.acc]
                aref[0, pl.ds(0, acc_w[step.acc])] = jnp.where(ok, vals, cur)
                continue
            if len(step.writes) == 1:
                vals = (vals,)
            for targets, val in zip(step.writes, vals):
                for wkind, wtgt in targets:
                    if wkind == "local":
                        local[str(wtgt)] = val
                    elif wkind == "buf" and str(wtgt) in pwin_of:
                        # producer plane window: the newest plane slot
                        # (p_lead tiles ahead), absolute row seating,
                        # predicated to the plane's row extent
                        pref, pw = pwin_of[str(wtgt)]
                        slot = _mod(opos[n_out - 1] + pw.p_lead,
                                    pw.p_stages)
                        r_idx = x + step.lead - pw.j_lo

                        @pl.when((r_idx >= 0) & (r_idx < win_h[pw.name]))
                        def _seat(_p=pref, _s=slot, _r=r_idx, _v=val,
                                  _c=step.out_col0 - pw.i_lo):
                            _p[_s, _r, pl.ds(_c, _v.shape[0])] = _v
                    elif wkind == "buf":
                        ref, b = ref_of[str(wtgt)]
                        stage = _mod(x + step.lead, b.stages)
                        ref[stage, pl.ds(step.out_col0 - b.i_lo,
                                         val.shape[0])] = val
                    else:  # 3. one output row for this grid step: the
                        # fill, then the value at its static lane offset
                        oref = o_refs[int(wtgt)]
                        orow = (0,) * n_out + (jax.lax.rem(jid, SUBLANE),)
                        oref[orow + (slice(None),)] = jnp.full(
                            (ni,), call.outputs[int(wtgt)].fill, val.dtype)
                        oref[orow + (pl.ds(step.out_col0, val.shape[0]),)] \
                            = val

        # 3b. dump accumulators into their revisited output blocks: the
        # final grid step (per kept tile for kept-prefix accumulators)
        # leaves the fully-combined row in place.
        for oi, out in enumerate(call.outputs):
            if out.acc is not None:
                aref, a = acc_of[out.acc]
                row = aref[0, pl.ds(0, acc_w[out.acc])]
                o_refs[oi][(0,) * (a.n_kept + 1) + (slice(None),)] = row

    grid = (*gsz, steps_j)
    in_specs = []
    out_specs = []
    out_shape = []
    for ispec in call.inputs:
        if ispec.scalar:
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            continue
        if double_buffer:
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            continue
        in_specs.append(pl.BlockSpec(
            (1,) * ispec.n_outer + (rb[ispec.name], in_w[ispec.name]),
            (lambda *ids, _sp=ispec:
             tuple(_outer_src(_sp, [ids[d] + o_lo[d] for d in range(n_out)]))
             + (_row_pos(_sp, ids[n_out] + call.x_lo) // rb[_sp.name], 0)),
        ))
    for out in call.outputs:
        if out.acc is not None:
            a = next(a for a in call.accs if a.name == out.acc)
            wa = acc_w[out.acc]
            k = a.n_kept
            out_specs.append(pl.BlockSpec(
                (1,) * k + (1, wa),
                lambda *ids, _k=k: tuple(ids[:_k]) + (0, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((*gsz[:k], 1, wa), dtype))
        else:
            out_specs.append(pl.BlockSpec(
                (1,) * n_out + (SUBLANE, ni),
                lambda *ids: tuple(ids[:n_out])
                + (ids[n_out] // SUBLANE, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((*gsz, out_rows, ni), dtype))

    scratch_shapes = [
        pltpu.VMEM((w.stages, _pad_to_lane(ni + (w.i_hi - w.i_lo))), dtype)
        for w in roll_wins
    ] + [
        pltpu.VMEM((i.p_stages, in_h[i.name], _pad_to_lane(in_w[i.name])),
                   dtype)
        for i in plane_ins
    ] + [
        pltpu.VMEM((w.p_stages, win_h[w.name],
                    _pad_to_lane(ni + (w.i_hi - w.i_lo))), dtype)
        for w in plane_wins
    ] + [
        pltpu.VMEM((1, _pad_to_lane(ni + a.w_off)), dtype)
        for a in call.accs
    ]
    if double_buffer and arr_ins:
        scratch_shapes += [
            pltpu.VMEM((2,) + (1,) * i.n_outer + (rb[i.name], dma_w[i.name]),
                       dtype)
            for i in arr_ins
        ]
        scratch_shapes.append(pltpu.SemaphoreType.DMA((len(arr_ins), 2)))
        scratch_shapes.append(pltpu.SMEM((len(arr_ins),), jnp.int32))
    need = call_vmem(call, nj, ni, jnp.dtype(dtype).itemsize,
                     double_buffer)["total"]
    if need > VMEM_CAPACITY:
        raise PallasUnsupported(
            f"call {call.name} needs {need} B of VMEM at sizes {sizes}; "
            f"a TPU core has {VMEM_CAPACITY} B")
    fn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        out_shape=out_shape if len(out_shape) > 1 else out_shape[0],
        scratch_shapes=scratch_shapes,
        # VMEM scratch carries state across every grid dim, so no dim
        # may be split across cores
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=scoped_vmem_limit(need)),
        interpret=interpret,
        name=f"hfav_{call.name}",
    )
    return fn, steps_j


# ---------------------------------------------------------------------------
# Host half + registration: size resolution, environment threading and
# output assembly are the interpreter-agnostic host half shared through
# the registry seam (repro.core.interpreters); this module contributes
# only the Pallas build_call.
# ---------------------------------------------------------------------------

def execute_plan(kplan: KernelPlan, *, dtype=jnp.float32,
                 interpret: Optional[bool] = None,
                 double_buffer: bool = False):
    """Build the host callable executing a full :class:`KernelPlan` on
    the Pallas stencil interpreter.

    A thin wrapper over the shared host half
    (:func:`repro.core.interpreters.execute_plan` with
    ``interpreter="pallas"``): the returned function takes the
    program's external arrays as keyword arguments and returns
    ``{store name: array}`` for every goal.  ``interpret`` defaults to
    interpret mode exactly where the default backend is not a TPU
    (:func:`repro.core.interpreters.resolve_interpret`);
    ``double_buffer=True`` selects the explicit two-slot async-DMA input
    pipeline."""
    from ...core.interpreters import execute_plan as _execute_plan
    return _execute_plan(kplan, interpreter="pallas", dtype=dtype,
                         interpret=interpret, double_buffer=double_buffer)


register_interpreter(InterpreterSpec(
    name="pallas",
    build_call=build_call,
    # the interpreter issues unit-stride lane slices only (a plan with
    # non-unit ReadPlan.i_stride must refuse, not miscompile), and it
    # does not yet execute LayoutApply's transformed constructs —
    # carried-vector slots, padded windows, lane-blocked accumulators
    capabilities=PLAN_FEATURES - frozenset({
        "strided_reads", "vec_loads", "align_pad", "lane_block"}),
    flags=frozenset({"interpret", "double_buffer"}),
    description="Pallas TPU stencil interpreter (VMEM windows, "
                "BlockSpec or double-buffered DMA row streaming)",
))
