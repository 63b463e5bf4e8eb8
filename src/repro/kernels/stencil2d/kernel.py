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

The nest is ``(*outer, cdiv(steps_j, R))`` tiles and row steps: the
plan's outer :class:`GridDim` entries map one-to-one onto outer tiles
and the row dim onto row steps, each covering its canonical range
``[lo, N_d + hi_off)`` — narrowed by halo'd goals and extended downward
by plane-window warm-up tiles.  The Pallas grid is one dimension that
numbers those steps in the nest's traversal order, last dimension
fastest, plus the steps that seating the external outputs needs
(:func:`repro.core.plancheck.seat_geometry`: one at the end, and any
tile or row step that only writes halo blocks), which compute nothing.
TPU grids execute sequentially, so VMEM scratch carries state both
across row tiles *and* across outer-tile boundaries.  Each grid step
computes a tile of R consecutive rows
(:func:`repro.core.plancheck.row_tile`: a multiple of the sublane tile
chosen from the plan, the dtype and the shape, or 1 for calls with
accumulators and for ``double_buffer=True``).  Row step ``jid`` covers
canonical positions ``jid * R + x_lo`` onward and:

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
   fill, then the values at their static lane offset).  An ``external``
   output (a goal) is written at its seat in a goal-shaped array
   (:class:`repro.core.plancheck.Seat`), rows and planes outside the
   goal masked to the fill: with R > 1 a goal block of R rows straddles
   two steps' tiles, so the step stores its rows in a VMEM carry buffer
   and the next step writes the block, from the carried rows and the
   first rows of its own tile; one row a step, the row goes to its goal
   row's place in the 8-row block that consecutive steps revisit, the
   block filled on its first visit.  Other row outputs go to the step's
   own R-row block, or the revisited 8-row block of one-row steps, in
   the grid's frame.  A block goes back to HBM when the grid moves to
   the next; accumulator outputs are dumped into a revisited block
   whose final grid step (per kept tile) holds the fully-combined
   partial-accumulator row.

Rolling windows are padded to the 128-wide TPU lane tile (the
vector-length expansion of Fig. 9c).  Warm-up/drain rows compute
garbage rows that the seat masks to the fill, or, for outputs in the
grid's frame, that :func:`execute_plan`'s host layer slices away — the
masked steady-state ('HFAV + Tuning') form.

All row widths in the plan are stored as *deltas against Ni* (and row
counts as deltas against Nj) so one plan serves every problem size; they
are concretized in :func:`build_call`.

:func:`execute_plan` is the host half of the interpreter: it resolves
runtime sizes through the plan's :class:`~repro.core.plan.AxiomPlan`
shape contracts, threads the environment between stencil calls and host
steps, hands each seated output on as it is and assembles the others
back to their canonical arrays (trim warm-up rows/tiles, re-seat origins,
lane-reduce folded accumulators) — exactly as the plan's
:class:`OutputPlan` trim/seat rules dictate.
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
                               row_tile, scoped_vmem_limit, seat_geometry)

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
    ``(fn, steps_j)``; the call maps the input arrays to one output per
    ``call.outputs`` entry (a list when there are several).
    ``external`` outputs are goal-shaped, ``sizes`` itself, every
    element outside the goal's rows, planes and lanes the output's
    ``fill``.  Other row outputs are ``(*grid, rows, ni)`` with ``rows``
    the ``steps_j`` canonical rows rounded up to a whole sublane tile;
    row ``t`` holds iteration position ``t + x_lo + out.lead``.
    Carried-accumulator outputs are ``(1, width)`` and kept-prefix
    accumulator outputs ``(*kept grid, 1, width)``.

    Each grid step computes a tile of R rows
    (:func:`repro.core.plancheck.row_tile`, from the plan, the dtype and
    the shape), so each tile has ``cdiv(steps_j, R)`` row steps and the
    last row block may be partial; the grid is one dimension of
    :func:`repro.core.plancheck.seat_geometry`'s steps.  Array inputs
    and row outputs move in R-row blocks, one per step, an external
    output's block written one step late from a VMEM carry buffer.
    Where R is 1 (calls with accumulators, or ``double_buffer=True``)
    they move in 8-row blocks that consecutive grid steps revisit, the
    step reading (or writing) its row at ``row % 8`` inside the block,
    ``goal row % 8`` for an external output.  ``double_buffer=True``
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
    seat_geo = seat_geometry(call, outer_sizes, nj, R, itemsize)
    seats = seat_geo.seats
    radix = seat_geo.radix
    n_tiled = seat_geo.steps - seat_geo.extra
    carried = [oi for oi in seats if R > 1]
    n_scratch = len(roll_wins) + len(plane_ins) + len(plane_wins) \
        + len(call.accs) + len(carried)

    def _digits(lin):
        """Tile of each outer dim and row step of grid step ``lin``, last
        digit fastest; the first digit is unbounded, so a step past the
        last tile reads as a tile past the last."""
        ds = []
        for r in reversed(radix[1:]):
            ds.append(jax.lax.rem(lin, r))
            lin = jax.lax.div(lin, r)
        return [lin] + ds[::-1]

    def _saturate(ds, lims):
        """``ds`` with the first digit at or past its limit, and every
        digit after it, at their last value; and whether one was."""
        over = False
        out = []
        for d, lim in zip(ds, lims):
            over = over | (d >= lim)
            out.append(jnp.where(over, lim - 1, d))
        return out, over

    def _at(lin):
        """The outer tile ids and the row step that grid step ``lin``
        computes, and whether it computes nothing: such a step (a tile or
        row step past the call's, or a step after the last tile) repeats
        the coordinates of the last step that computes, so its input
        blocks stay where they are."""
        ds, idle = _saturate(_digits(lin), (*gsz, geo.steps))
        return ds[:n_out], ds[n_out], idle

    def _seat_at(seat, lin):
        """What grid step ``lin`` writes of a seated output: the goal
        plane of each outer dim, the row block, whether it writes, and the
        row step it was counted from.  With R > 1 the step writes block
        ``k`` of the tile whose row step ``k`` ran ``skew // R + 1`` steps
        earlier; one row a step, the block that holds its goal row.  A
        step that writes nothing keeps the block of the last step that
        did (or of the first that will), so no block is visited twice in
        a row of steps but by the steps that write it."""
        v = lin - (seat.skew // R + 1 if R > 1 else 0)
        ds = _digits(jnp.clip(v, 0, n_tiled - 1))
        k = ds[n_out]
        blk = k if R > 1 else jnp.minimum(
            jax.lax.div(jnp.maximum(k - seat.skew, 0), seat.block),
            seat.blocks - 1)
        u = [ds[d] + seat.first[d] - seat.window[d] for d in range(n_out)]
        (*u, blk), over = _saturate(u + [blk], (*outer_sizes, seat.blocks))
        planes = [_mod(u[d] + seat.window[d], outer_sizes[d])
                  for d in range(n_out)]
        writes = (v >= 0) & (v < n_tiled) & jnp.logical_not(over)
        return planes, blk, writes, k

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
        """Canonical outer positions and row-grid index of the
        ``lin``-th step that computes (the nest's steps, last dimension
        fastest; steps that compute nothing are not counted)."""
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
        n_fixed = len(roll_wins) + len(plane_ins) + len(plane_wins)
        acc_of = {a.name: (r, a) for r, a in zip(scratch[n_fixed:],
                                                 call.accs)}
        carry_of = dict(zip(carried, scratch[n_fixed + len(call.accs):]))

        step_id = pl.program_id(0)
        outer_ids, jid, idle = _at(step_id)
        opos = [outer_ids[d] + o_lo[d] for d in range(n_out)]
        # this step's first canonical position, and the row-grid offset
        # of its R rows (a multiple of R, for aligned plane accesses)
        x0 = jid * R + call.x_lo
        base = jid if R == 1 else pl.multiple_of(jid * R, R)
        at_seat = {oi: _seat_at(seat, step_id) for oi, seat in seats.items()}
        no_outer = (0,) * n_out

        # 0. seated outputs, before the step's rows: with R > 1 the block
        # written now takes its first rows from those the step before
        # carried; one row a step, a block is filled on its first visit,
        # so rows no step computes hold the fill
        for oi, seat in seats.items():
            fill = call.outputs[oi].fill
            _, _, writes, k = at_seat[oi]
            oref = o_refs[oi]
            if R > 1:
                m = seat.skew % R
                keep = min(R - m, seat.block)
                src = (pl.ds(seat.pad + m, keep), pl.ds(0, ni))
                if seat.skew < 0:
                    # the first block's carried rows are never computed
                    @pl.when(step_id == 0)
                    def _clear(_c=carry_of[oi], _src=src, _f=fill, _n=keep):
                        _c[_src] = jnp.full((_n, ni), _f, dtype)

                @pl.when(writes)
                def _carried(_o=oref, _c=carry_of[oi], _src=src, _n=keep):
                    _o[no_outer + (pl.ds(0, _n), slice(None))] = _c[_src]
            else:
                g = k - seat.skew
                fresh = writes & ((k == 0) | (
                    (g > 0) & (g < seat.block * seat.blocks)
                    & (jax.lax.rem(g, seat.block) == 0)))

                @pl.when(fresh)
                def _clear(_o=oref, _f=fill, _b=seat.block):
                    _o[no_outer + (slice(None), slice(None))] = \
                        jnp.full((_b, ni), _f, dtype)

        def _seat_rows(oi, step, val):
            """A step's rows of a seated output, ``fill`` outside the
            goal's rows and planes: into the carry buffer (R > 1), or at
            the goal row's place in its block (one row a step)."""
            seat = seats[oi]
            out = call.outputs[oi]
            lead = out.outer_lead or no_outer
            # the goal rows, counted from this step's first row
            g0 = jid * R - seat.skew
            lo, hi = out.j_lo - g0, nj + out.j_hi - g0
            if n_out:
                inside = functools.reduce(operator.and_, [
                    (opos[d] + lead[d] >= out.outer_lo[d])
                    & (opos[d] + lead[d] < outer_sizes[d] + out.outer_hi[d])
                    for d in range(n_out)])
                lo, hi = jnp.where(inside, lo, 0), jnp.where(inside, hi, 0)
            w = val.shape[1]
            whole = step.out_col0 == 0 and w == ni
            if R > 1:
                r = jax.lax.broadcasted_iota(jnp.int32, val.shape, 0)
                rows = pl.ds(seat.pad, R)
                if not whole:
                    carry_of[oi][rows, pl.ds(0, ni)] = \
                        jnp.full((R, ni), out.fill, dtype)
                carry_of[oi][rows, pl.ds(step.out_col0, w)] = \
                    jnp.where((r >= lo) & (r < hi), val, out.fill)
                return

            @pl.when(at_seat[oi][2] & (g0 >= 0) & (g0 < nj))
            def _row():
                at = no_outer + (pl.ds(jax.lax.rem(g0, seat.block), 1),)
                if not whole:
                    o_refs[oi][at + (slice(None),)] = \
                        jnp.full((1, ni), out.fill, dtype)
                o_refs[oi][at + (pl.ds(step.out_col0, w),)] = \
                    jnp.where((lo <= 0) & (hi > 0), val, out.fill)

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

        def _compute():
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
                # Linear odometer: `lin` enumerates the steps that compute,
                # in execution order; the neighbours decide whether the row
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
                        elif int(wtgt) in seats:
                            _seat_rows(int(wtgt), step, val)
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

        if seat_geo.steps > total_steps:
            pl.when(jnp.logical_not(idle))(_compute)

            @pl.when(idle)
            def _no_rows():
                for oi in carried:
                    carry_of[oi][pl.ds(seats[oi].pad, R), pl.ds(0, ni)] = \
                        jnp.full((R, ni), call.outputs[oi].fill, dtype)
        else:
            _compute()

        # 4. seated outputs, after the step's rows: the block written now
        # (R > 1) ends with this step's first rows
        for oi in carried:
            seat = seats[oi]
            m = seat.skew % R
            n_cur = seat.block - (R - m)
            if n_cur > 0:
                @pl.when(at_seat[oi][2])
                def _own(_o=o_refs[oi], _c=carry_of[oi], _s=seat, _m=m,
                         _n=n_cur):
                    _o[no_outer + (pl.ds(R - _m, _n), slice(None))] = \
                        _c[pl.ds(_s.pad, _n), pl.ds(0, ni)]

    def _in_index(ispec, lin):
        oids, jid, _ = _at(lin)
        return (*_outer_src(ispec, [oids[d] + o_lo[d] for d in range(n_out)]),
                _src_row(ispec, jid) // rb[ispec.name], 0)

    def _seat_index(seat, lin):
        planes, blk, _, _ = _seat_at(seat, lin)
        return (*planes, blk, 0)

    def _row_index(lin):
        oids, jid, _ = _at(lin)
        return (*oids, jid * R // geo.out_block, 0)

    grid = (seat_geo.steps,)
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
            functools.partial(_in_index, ispec)))
    for oi, out in enumerate(call.outputs):
        if out.acc is not None:
            a = next(a for a in call.accs if a.name == out.acc)
            wa = acc_w[out.acc]
            k = a.n_kept
            out_specs.append(pl.BlockSpec(
                (1,) * k + (1, wa),
                lambda lin, _k=k: (*_at(lin)[0][:_k], 0, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((*gsz[:k], 1, wa), dtype))
        elif oi in seats:
            out_specs.append(pl.BlockSpec(
                (1,) * n_out + (seats[oi].block, ni),
                functools.partial(_seat_index, seats[oi])))
            out_shape.append(
                jax.ShapeDtypeStruct((*outer_sizes, nj, ni), dtype))
        else:
            out_specs.append(pl.BlockSpec(
                (1,) * n_out + (geo.out_block, ni), _row_index))
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
    ] + [
        pltpu.VMEM((-(-(seats[oi].pad + R) // geo.tile) * geo.tile,
                    _pad_to_lane(ni)), dtype)
        for oi in carried
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
