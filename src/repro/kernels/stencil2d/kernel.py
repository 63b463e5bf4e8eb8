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

The grid is ``(*outer, cdiv(steps_j, R))``: the plan's outer
:class:`GridDim` entries map one-to-one onto leading grid dimensions and
the row dim onto the last, each covering its canonical range
``[lo, N_d + hi_off)`` — narrowed by halo'd goals and extended downward
by plane-window warm-up tiles.  Each grid step computes a tile of R
consecutive rows (:func:`repro.core.plancheck.row_tile`: a multiple of
the sublane tile chosen from the plan, the dtype and the shape, or 1 for
calls with accumulators and for ``double_buffer=True``).  TPU grids
execute sequentially with the last dimension fastest, which is exactly
the fused nest's traversal order — VMEM scratch therefore carries state
both across row tiles *and* across outer-tile boundaries.  Grid step
``jid`` covers canonical positions ``jid * R + x_lo`` onward and:

1. takes R new rows per array input into that input's VMEM window,
   ``lead`` rows ahead of the canonical point.  With R > 1 each step
   brings one R-row block through the BlockSpec index map, the last
   block of a ragged array partial; an input whose first row
   ``x_lo + lead - j_lo`` is not on the tile is brought in that many
   rows ahead.  One-row steps take their row from an 8-row
   (sublane-tile) group that consecutive grid steps revisit — Mosaic
   refuses one-row blocks — either through the BlockSpec index map, or,
   with ``double_buffer=True``, through an explicitly double-buffered
   ``make_async_copy`` pair that prefetches the next group while the
   current row is being consumed.  Inputs read at non-zero offsets in
   the *plane dim* (the outer identifier adjacent to the row dim —
   ``u[k-1][j][i]`` stencils) use a *multi-plane window* instead of a
   rolling row window: ``(p_stages, rows, width)`` VMEM where whole
   planes stay resident across outer tiles and the streamed rows land
   in the newest plane at their absolute row index, ``p_lead`` tiles
   ahead (Fig. 9a/9b applied one loop level further out);
2. executes every fused step at its software-pipeline lead over its R
   rows, each rule applied row by row (``jax.vmap``), so every element
   is made by the same arithmetic as with one row a step.  A rolling
   window is a linear buffer of ``R + stages - 1`` rows: at the start of
   a step the ``stages - 1`` rows that the step still reads move up
   above the new rows (the paper's pointer rotation, Fig. 9a/9b, done as
   one copy a tile), and every read is an ``(R, w)`` slice at a static
   row offset.  Neighbor *planes* sit in mod-``p_stages`` plane slots,
   rows at their absolute index below a top margin; an R-row step loads
   each slot's rows once from an aligned row offset and slices its reads
   out of them.  Variables *produced in the nest* and read at plane
   offsets write a **producer plane window**
   (:class:`~repro.core.plan.WindowPlan` in plane mode): the producing
   step runs ``p_lead`` tiles ahead in the plane dim and seats its rows
   at their absolute plane-row index, so ``v[k-1][j][i]``-style
   consumers read older resident planes without a round-trip through
   HBM.  Reduction steps (one-row steps only) combine into VMEM
   accumulator rows carried across grid steps (the vector partial
   accumulators of Section 3.5), predicated on the canonical point being
   inside the reduced extent (rows *and* outer tiles) — carried across
   the whole grid or re-initialized per kept-prefix tile
   (:attr:`~repro.core.plan.AccPlan.n_kept`); row-kept reductions carry
   nothing and emit one identity-padded partial row per position
   instead;
3. writes its rows of each terminal output as one ``(R, ni)`` store (the
   fill, then the values at their static lane offset) into its output
   block, R rows, or the revisited 8-row block of one-row steps, which
   goes back to HBM when the grid moves to the next block; accumulator
   outputs are dumped into a revisited block whose final grid step (per
   kept tile) holds the fully-combined partial-accumulator row.

Rolling windows are padded to the 128-wide TPU lane tile (the
vector-length expansion of Fig. 9c).  Warm-up/drain rows compute
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
from ...core.plancheck import (VMEM_CAPACITY, call_vmem, row_geometry,
                               row_tile, scoped_vmem_limit)

LANE = 128
#: Rows per streamed block of one-row grid steps: one f32 sublane tile.
#: Mosaic accepts a block only when its last two dims are multiples of
#: (8, 128) or equal to the array's, so those rows move between HBM and
#: VMEM in 8-row groups that consecutive grid steps revisit.
SUBLANE = 8


def _pad_to_lane(w: int) -> int:
    return max(LANE, ((w + LANE - 1) // LANE) * LANE)


def _mod(pos, stages: int):
    """Floor-mod robust to negative pipeline-priming positions."""
    return jax.lax.rem(jax.lax.rem(pos, stages) + stages, stages)


def _per_row(fn, ins):
    """Apply a rule written for one row to R rows: ``jax.vmap`` over the
    row axis of every 2-D operand, scalars shared."""
    axes = tuple(0 if jnp.ndim(v) == 2 else None for v in ins)
    return jax.vmap(fn, in_axes=axes)(*ins)


def build_call(call: CallPlan, sizes: tuple[int, ...], dtype,
               interpret: bool = False, double_buffer: bool = False):
    """Concretize one :class:`CallPlan` for a problem size and build the
    pallas_call.

    ``sizes`` is ``(*outer_sizes, Nj, Ni)`` with ``call.n_outer`` leading
    outer extents (``(Nj, Ni)`` for a plain 2-D nest).  Returns
    ``(fn, steps_j)``; the call maps the input arrays to one padded
    output per ``call.outputs`` entry (a list when there are several).
    Row outputs are ``(*grid, rows, ni)`` with ``rows`` the ``steps_j``
    canonical rows rounded up to a whole sublane tile; row ``t`` holds
    iteration position ``t + x_lo + out.lead``.  Carried-accumulator
    outputs are ``(1, width)`` and kept-prefix accumulator outputs
    ``(*kept grid, 1, width)``.

    Each grid step computes a tile of R rows
    (:func:`repro.core.plancheck.row_tile`, from the plan, the dtype and
    the shape), so the row grid dim has ``cdiv(steps_j, R)`` steps and
    the last row block may be partial.  Array inputs and row outputs
    move in R-row blocks, one per step.  Where R is 1 (calls with
    accumulators, or ``double_buffer=True``) they move in 8-row blocks
    that consecutive grid steps revisit, the step reading (or writing)
    its row at ``row % 8`` inside the block.  ``double_buffer=True``
    replaces the BlockSpec input streaming with an explicit two-slot
    async-DMA pipeline: array inputs stay in HBM
    (``memory_space=ANY``); whenever the next grid step needs a new
    8-row group, the current step starts its copy into the other slot,
    so the input DMA overlaps the compute of the current row.

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
    itemsize = jnp.dtype(dtype).itemsize
    R = row_tile(call, nj, ni, itemsize, double_buffer)
    geo = row_geometry(call, nj, R, itemsize)
    total_steps = geo.steps
    for s in gsz:
        total_steps *= s

    arr_ins = [i for i in call.inputs if not i.scalar]
    row_ins = [i for i in arr_ins if not i.plane]
    plane_ins = [i for i in arr_ins if i.plane]
    roll_wins = [WindowPlan(f"in_{i.name}", i.stages, i.i_lo, i.i_hi)
                 for i in row_ins] + [w for w in call.windows if not w.plane]
    plane_wins = [w for w in call.windows if w.plane]
    roll_lo = {w.name: w.i_lo for w in roll_wins}
    pwin_of = {w.name: w for w in plane_wins}
    acc_w = {a.name: ni + a.w_off for a in call.accs}
    ref_idx = {ispec.name: k for k, ispec in enumerate(call.inputs)}
    in_h = {i.name: nj + (i.j_hi - i.j_lo) for i in arr_ins}
    in_w = {i.name: ni + (i.i_hi - i.i_lo) for i in arr_ins}
    # plane buffers by read/write name: (j_lo, i_lo, p_stages)
    plane_src = {f"in_{i.name}": (i.j_lo, i.i_lo, i.p_stages)
                 for i in plane_ins}
    plane_src.update({w.name: (w.j_lo, w.i_lo, w.p_stages)
                      for w in plane_wins})
    rb = geo.block
    # lanes per explicit DMA: Mosaic copies whole lane tiles, reaching
    # into the lane padding of the TPU's tiled HBM layout, which the
    # interpreter's arrays do not have
    dma_w = {n: w if interpret else _pad_to_lane(w) for n, w in in_w.items()}
    n_scratch = len(roll_wins) + len(plane_ins) + len(plane_wins) \
        + len(call.accs)

    def _src_row(ispec, j_id):
        """First source row of ``ispec``'s block for row-grid step
        ``j_id`` (clamped: warm-up and drain steps fetch edge blocks,
        whose rows feed only positions the host trims away)."""
        return jnp.clip(j_id * R + geo.first_row[ispec.name], 0,
                        geo.last_row[ispec.name])

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
        """Where ``ispec``'s row for one grid step sits in HBM (one-row
        steps): its outer source indices, the first row of its
        ``rb``-row group and the row's offset inside the group.  On the
        TPU groups start on a sublane tile, so the last one of an array
        whose rows are not a multiple of 8 reaches into the tile
        padding, as the BlockSpec pipeline's own ragged last block does;
        the interpreter, whose arrays have no padding, shifts that group
        up instead."""
        r = _src_row(ispec, j_id)
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
        j_id = jax.lax.rem(lin, geo.steps)
        rest = jax.lax.div(lin, geo.steps)
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
        roll_of = dict(zip((w.name for w in roll_wins), scratch))
        plane_of = dict(zip(
            [f"in_{i.name}" for i in plane_ins] + [w.name for w in plane_wins],
            scratch[len(roll_wins):]))
        acc_of = {a.name: (r, a) for r, a in zip(
            scratch[len(roll_wins) + len(plane_ins) + len(plane_wins):],
            call.accs)}

        outer_ids = [pl.program_id(d) for d in range(n_out)]
        opos = [outer_ids[d] + o_lo[d] for d in range(n_out)]
        jid = pl.program_id(n_out)
        # this step's first canonical position, and the row-grid offset
        # of its R rows (a multiple of R, for aligned plane accesses)
        x0 = jid * R + call.x_lo
        base = jid if R == 1 else pl.multiple_of(jid * R, R)

        def _plane_slot(name, p_off):
            return _mod(opos[n_out - 1] + p_off, plane_src[name][2])

        def _store_rows(ispec, rows, src_row):
            """Seat a step's freshly-streamed rows: below the kept halo
            of a rolling row window, or at their absolute row index in
            the newest plane of a plane window (``p_lead`` tiles
            ahead)."""
            name = f"in_{ispec.name}"
            cols = pl.ds(0, in_w[ispec.name])
            if ispec.plane:
                at = geo.margin[name] + src_row
                if R > 1:
                    at = pl.multiple_of(at, geo.tile)
                plane_of[name][_plane_slot(name, ispec.p_lead),
                               pl.ds(at, rows.shape[0]), cols] = rows
            else:
                roll_of[name][pl.ds(geo.halo[name][1], rows.shape[0]),
                              cols] = rows

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
                r[pl.ds(0, 1), :] = jnp.full((1, r.shape[1]), _a.init, dtype)

        # 1a. rolling windows: the rows the previous step computed last
        # that this step still reads move up above the new rows
        for name, ref in roll_of.items():
            keep, at = geo.halo[name]
            if keep:
                ref[pl.ds(at - keep, keep), :] = \
                    ref[pl.ds(at + R - keep, keep), :]

        # 1b. stream each array input's new rows into its VMEM window
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
            mult = geo.steps
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

                rows = dma_stage[ispec.name][
                    (slot,) + (0,) * ispec.n_outer
                    + (pl.ds(here[2], 1), pl.ds(0, in_w[ispec.name]))]
                _store_rows(ispec, rows, here[1] + here[2])
        else:
            for ispec in arr_ins:
                src = in_refs[ref_idx[ispec.name]]
                r = _src_row(ispec, jid)
                if R == 1:
                    rows = src[(0,) * ispec.n_outer
                               + (pl.ds(jax.lax.rem(r, rb[ispec.name]), 1),
                                  slice(None))]
                else:
                    rows = src[(0,) * ispec.n_outer
                               + (slice(None), slice(None))]
                _store_rows(ispec, rows, r)

        # 2. fused steps, in dataflow order, at their leads, each over
        # the step's R rows
        local: dict[str, jnp.ndarray] = {}
        loaded: dict = {}  # (plane buffer, p_off) -> its rows of this step

        def _plane_read(name, rd, w):
            """``(R, w)`` rows of a plane buffer for one read: absolute
            rows of the plane slot at ``rd.p_off``.  One-row steps load
            the row directly; R-row steps load each slot's aligned read
            span once and slice every read out of it."""
            j_lo, i_lo, _ = plane_src[name]
            slot = _plane_slot(name, rd.p_off)
            row = geo.margin[name] + call.x_lo + rd.j_off - j_lo
            col = rd.col0 - i_lo
            if R == 1:
                return plane_of[name][slot, pl.ds(base + row, 1),
                                      pl.ds(col, w)]
            first, n = geo.span[name]
            key = (name, rd.p_off)
            if key not in loaded:
                loaded[key] = plane_of[name][slot, pl.ds(
                    pl.multiple_of(base + first, geo.tile), n), :]
            return loaded[key][row - first:row - first + R, col:col + w]

        for step in call.steps:
            ins = []
            cur = None
            if step.acc is not None:
                aref, _ = acc_of[step.acc]
                cur = aref[pl.ds(0, 1), pl.ds(0, acc_w[step.acc])]
                ins.append(cur)
            for rd in step.reads:
                w = ni + rd.w_off
                if rd.src.startswith("local:"):
                    ins.append(local[rd.src[6:]][:, rd.col0:rd.col0 + w])
                elif rd.src.startswith("scalar:"):
                    sref = in_refs[ref_idx[rd.src[7:]]]
                    ins.append(sref[0, 0])
                elif rd.src in plane_src:
                    ins.append(_plane_read(rd.src, rd, w))
                else:
                    # rolling window: static rows relative to the newest
                    row = geo.halo[rd.src][1] + rd.j_off - geo.lead[rd.src]
                    ins.append(roll_of[rd.src][
                        pl.ds(row, R), pl.ds(rd.col0 - roll_lo[rd.src], w)])
            vals = _per_row(call.fns[step.fn_idx], ins)
            if step.acc is not None:
                # predicated combine, one mask row per position:
                # warm-up/drain rows *and* tiles must not pollute
                lo, hi = step.valid
                pos = x0 + step.lead + jax.lax.broadcasted_iota(
                    jnp.int32, (R, 1), 0)
                ok = (pos >= lo) & (pos < nj + hi)
                for d, (vlo, vhi) in enumerate(step.valid_outer):
                    ok &= (opos[d] >= vlo) & (opos[d] < outer_sizes[d] + vhi)
                aref, _ = acc_of[step.acc]
                aref[pl.ds(0, 1), pl.ds(0, acc_w[step.acc])] = \
                    jnp.where(ok, vals, cur)
                continue
            if len(step.writes) == 1:
                vals = (vals,)
            for targets, val in zip(step.writes, vals):
                for wkind, wtgt in targets:
                    if wkind == "local":
                        local[str(wtgt)] = val
                    elif wkind == "buf" and str(wtgt) in plane_src:
                        # producer plane window: the newest plane slot
                        # (p_lead tiles ahead), rows at their absolute
                        # index below the window's top margin
                        name = str(wtgt)
                        pw = pwin_of[name]
                        at = geo.margin[name] + call.x_lo + step.lead \
                            - pw.j_lo + base
                        if R > 1:
                            at = pl.multiple_of(at, geo.tile)
                        plane_of[name][
                            _plane_slot(name, pw.p_lead), pl.ds(at, R),
                            pl.ds(step.out_col0 - pw.i_lo, val.shape[1])] \
                            = val
                        for key in [k for k in loaded if k[0] == name]:
                            del loaded[key]
                    elif wkind == "buf":
                        name = str(wtgt)
                        row = geo.halo[name][1] + step.lead - geo.lead[name]
                        roll_of[name][pl.ds(row, R),
                                      pl.ds(step.out_col0 - roll_lo[name],
                                            val.shape[1])] = val
                    else:  # 3. the step's output rows: the fill, then
                        # the values at their static lane offset
                        oref = o_refs[int(wtgt)]
                        at = (0,) * n_out + (pl.ds(
                            0 if R > 1 else jax.lax.rem(jid, SUBLANE), R),)
                        oref[at + (slice(None),)] = jnp.full(
                            (R, ni), call.outputs[int(wtgt)].fill, val.dtype)
                        oref[at + (pl.ds(step.out_col0, val.shape[1]),)] \
                            = val

        # 3b. dump accumulators into their revisited output blocks: the
        # final grid step (per kept tile for kept-prefix accumulators)
        # leaves the fully-combined row in place.
        for oi, out in enumerate(call.outputs):
            if out.acc is not None:
                aref, a = acc_of[out.acc]
                o_refs[oi][(0,) * a.n_kept + (pl.ds(0, 1), slice(None))] = \
                    aref[pl.ds(0, 1), pl.ds(0, acc_w[out.acc])]

    grid = (*gsz, geo.steps)
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
             + (_src_row(_sp, ids[n_out]) // rb[_sp.name], 0)),
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
                (1,) * n_out + (geo.out_block, ni),
                lambda *ids: tuple(ids[:n_out])
                + (ids[n_out] * R // geo.out_block, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((*gsz, out_rows, ni), dtype))

    scratch_shapes = [
        pltpu.VMEM((geo.height[w.name],
                    _pad_to_lane(ni + (w.i_hi - w.i_lo))), dtype)
        for w in roll_wins
    ] + [
        pltpu.VMEM((i.p_stages, geo.height[f"in_{i.name}"],
                    _pad_to_lane(in_w[i.name])), dtype)
        for i in plane_ins
    ] + [
        pltpu.VMEM((w.p_stages, geo.height[w.name],
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
    need = call_vmem(call, nj, ni, itemsize, double_buffer, rows=R)["total"]
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
