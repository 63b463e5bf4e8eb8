"""The Pallas interpreter's tiling: how one stencil call is cut into
grid steps and laid out in VMEM.

:func:`call_tiling` is the one entry point the executor reads.  For a
call at concrete sizes and dtype it gives the rows R each grid step
computes (:func:`row_tile`), where every buffer's rows sit at that R
(:func:`row_geometry`), the grid that seats the call's ``external``
outputs (:func:`seat_geometry`), the VMEM the call needs
(:func:`call_vmem`) and the scoped VMEM limit to compile it with
(:func:`scoped_vmem_limit`).  ``kernels/stencil2d/kernel.py``
``build_call`` builds its ``pallas_call`` from that result, and the
host half (:mod:`repro.core.interpreters`) counts its grid steps and
VMEM from the same one; the static analyzer (:mod:`repro.core.plancheck`)
reads :func:`call_vmem` for its VMEM budget check (PC003).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence

from .plan import CallPlan, PallasUnsupported, StepPlan

#: The Mosaic compiler's default scoped VMEM limit on TPU v5e (16 MiB):
#: a kernel that needs more is compiled with a raised limit.
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024

#: Physical VMEM of one TPU v5e TensorCore (128 MiB): the most a
#: kernel's scoped limit may be raised to.
VMEM_CAPACITY = 128 * 1024 * 1024

#: TPU lane width: every resident row is padded to a multiple of it.
LANE = 128
#: Rows per streamed block of one-row grid steps: one f32 sublane tile.
#: Mosaic accepts a block only when its last two dims are multiples of
#: (8, 128) or equal to the array's, so those rows move between HBM and
#: VMEM in 8-row groups that consecutive grid steps revisit.
SUBLANE = 8


def pad_to_lane(w: int) -> int:
    """Lane-pad one row width: the interpreter allocates every resident
    row at a multiple of :data:`LANE` elements (minimum one lane).
    Shared with :mod:`repro.core.vecscan`'s occupancy model."""
    return max(LANE, ((w + LANE - 1) // LANE) * LANE)


def row_tile_unit(dtype_bytes: int) -> int:
    """Rows of one sublane tile: 8 of 32-bit values, 16 of 2-byte ones."""
    return SUBLANE * max(1, 4 // int(dtype_bytes))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad_rows(rows: int, dtype_bytes: int) -> int:
    """Rows a VMEM buffer occupies: a multiple of the sublane tile
    (8 rows of 32-bit values, more for narrower ones)."""
    return _round_up(rows, row_tile_unit(dtype_bytes))


#: Most rows one grid step computes: the cap of the row tile R.  On a
#: TPU v5e, kernel time fell with R up to 128, the largest R tried, in
#: both chip benchmark cells (PERF.md, "Findings").
ROW_TILE_CAP = 128


@dataclass(frozen=True)
class RowGeometry:
    """Where one call's rows sit when each grid step computes ``rows``
    (R) of them: what the Pallas interpreter's ``build_call`` allocates
    and indexes, and what :func:`call_vmem` counts.

    Grid step ``jid`` computes canonical positions ``jid * R + x_lo``
    onward.  An array input's step brings the block whose first source
    row is ``jid * R + first_row``, clamped to ``[0, last_row]``; that is
    ``lookahead`` rows beyond ``x_lo + lead - j_lo``, so that every block
    starts on a multiple of R.  A rolling window keeps ``halo[name] =
    (keep, at)``: the newest R rows land at row ``at`` and the ``keep``
    rows above them carry over from the step before; ``lead[name]`` is
    the position lead of those newest rows (a window's writer at a
    lower lead stores that many rows higher up).  A plane buffer keeps
    absolute rows below a top ``margin``; for R > 1 each step loads its
    reads in one aligned ``span[name] = (first, rows)`` window at row
    ``jid * R + first``.  ``height`` is every buffer's row count."""

    rows: int
    tile: int
    steps: int
    out_block: int
    block: dict
    first_row: dict
    last_row: dict
    lead: dict
    halo: dict
    margin: dict
    span: dict
    height: dict


def row_geometry(call: CallPlan, nj: int, rows: int,
                 dtype_bytes: int) -> RowGeometry:
    """The buffers of ``call`` at row tile ``rows`` and row count ``nj``
    (see :class:`RowGeometry`)."""
    R = int(rows)
    t = row_tile_unit(dtype_bytes)
    steps = -(-(nj + call.x_hi_off - call.x_lo) // R)
    writers = call.writers()
    block, first, last, lead, halo, margin, span, height = \
        {}, {}, {}, {}, {}, {}, {}, {}
    for i in call.inputs:
        if i.scalar:
            continue
        in_h = nj + i.j_hi - i.j_lo
        s = call.x_lo + i.lead - i.j_lo
        block[i.name] = min(SUBLANE if R == 1 else R, in_h)
        first[i.name] = s + (-s) % R
        last[i.name] = in_h - 1 if R == 1 else \
            (-(-in_h // block[i.name]) - 1) * block[i.name]
        if not i.plane:
            lead[f"in_{i.name}"] = i.lead + (-s) % R
            halo[f"in_{i.name}"] = i.stages + (-s) % R - 1
    for w in call.windows:
        if w.plane:
            continue
        lead[w.name] = max((call.steps[si].lead
                            for si in writers.get(w.name, ())), default=0)
        halo[w.name] = w.stages - 1
    for name, keep in halo.items():
        at = keep if R == 1 else _round_up(keep, t)
        halo[name] = (keep, at)
        height[name] = at + R
    planes = [(f"in_{i.name}", i.j_lo, i) for i in call.inputs if i.plane]
    planes += [(w.name, w.j_lo, None) for w in call.windows if w.plane]
    for name, j_lo, ispec in planes:
        reads = [call.x_lo + rd.j_off - j_lo for s_ in call.steps
                 for rd in s_.reads if rd.src == name]
        writes = [call.x_lo + call.steps[si].lead - j_lo
                  for si in writers.get(name, ())]
        m = _round_up(max(0, -min(reads + writes, default=0)), t)
        if R > 1 and writes:
            m += -(m + writes[0]) % t
        ends = [m]
        if ispec is not None:
            in_h = nj + ispec.j_hi - ispec.j_lo
            ends.append(m + (in_h if R == 1 else
                             -(-in_h // block[ispec.name]) * block[ispec.name]))
        if writes:
            ends.append((steps - 1) * R + m + max(writes) + R)
        if reads and R == 1:
            ends.append(steps + m + max(reads))
        elif reads:
            lo = (m + min(reads)) // t * t
            span[name] = (lo, _round_up(m + max(reads) + R - lo, t))
            ends.append((steps - 1) * R + sum(span[name]))
        margin[name] = m
        height[name] = _round_up(max(ends), t)
    return RowGeometry(R, t, steps, SUBLANE if R == 1 else R, block, first,
                       last, lead, halo, margin, span, height)


@dataclass(frozen=True)
class Seat:
    """Where the Pallas interpreter writes one ``external`` output, which
    it returns goal-shaped, ``(*N_outer, Nj, Ni)``, every element outside
    the goal's rows, planes and lanes equal to the output's ``fill``.

    Goal row ``g`` is computed at row position ``g + skew`` of the row
    grid (``skew = -(x_lo + lead)``).  The output moves in blocks of
    ``block`` rows, ``blocks`` of them per plane.  With R > 1 the block
    of R goal rows straddles two steps' tiles: it is written one grid
    step after the step that computed most of it (``skew // R + 1``
    steps after the row step of the same index), from the rows that
    step left in a carry buffer and the first ``skew % R`` rows of the
    step's own tile.  The carry buffer keeps ``pad`` rows above the
    step's rows, so that the carried rows start on a sublane tile.  One
    row a step, the row goes to row ``g % block`` of the block that
    consecutive steps revisit.

    Along outer dim ``d`` the grid's ``t``-th tile computes plane
    ``t + first[d]``.  The ``N_d`` tiles from plane ``window[d]`` on
    write planes ``c mod N_d``, so the warm-up and drain tiles that
    compute no goal plane write ``fill`` into the halo planes that no
    goal tile covers; tiles before the window write planes that a tile
    of the window writes again later, and tiles after it write
    nothing."""

    skew: int
    block: int
    blocks: int
    pad: int
    first: tuple
    window: tuple


@dataclass(frozen=True)
class SeatGeometry:
    """The grid of one call whose ``external`` outputs are seated
    (:func:`seat_geometry`): ``radix`` is the tiles of each outer dim
    and the row steps of each tile, ``extra`` the steps after the last
    tile, and ``seats`` the :class:`Seat` of each external output by its
    index in ``call.outputs``.  The Pallas grid is one dim of
    ``steps`` steps, numbered in the order the TPU runs them; the steps
    past the ones that compute (a tile past the grid's, a row step past
    its tile's) compute nothing."""

    radix: tuple
    extra: int
    seats: dict

    @property
    def steps(self) -> int:
        n = self.extra
        prod = 1
        for r in self.radix:
            prod *= r
        return n + prod


def _row_seat(call: CallPlan, out, nj: int, R: int, tile: int):
    """``(skew, block, blocks, pad)`` of one external output at row tile
    ``R`` (see :class:`Seat`)."""
    skew = -(call.x_lo + out.lead)
    block = min(SUBLANE if R == 1 else R, nj)
    if (R == 1 and skew <= -block) or (R > 1 and skew // R < -1):
        raise PallasUnsupported(
            f"call {call.name}: output {out.name} is computed "
            f"{-skew} rows ahead of its goal rows, more than one block")
    pad = (-(skew % R)) % tile if R > 1 else 0
    return skew, block, -(-nj // block), pad


def seat_geometry(call: CallPlan, outer_sizes: Sequence[int], nj: int,
                  rows: int, dtype_bytes: int) -> SeatGeometry:
    """The grid that seats every ``external`` output of ``call`` at row
    tile ``rows`` (see :class:`SeatGeometry`).  Each outer dim has at
    least the tiles its goal planes and halo planes need, each tile at
    least the row steps that reach every block of the output (R > 1) or
    every 8-row block (one row a step), and the grid enough steps after
    the last tile for the last block written late."""
    R = int(rows)
    t = row_tile_unit(dtype_bytes)
    n_out = call.n_outer
    tiles = [outer_sizes[d] + call.outer_hi_off[d] - call.outer_lo[d]
             for d in range(n_out)]
    row_steps = -(-(nj + call.x_hi_off - call.x_lo) // R)
    seats = {}
    for oi, out in enumerate(call.outputs):
        if out.kind != "external":
            continue
        skew, block, blocks, pad = _row_seat(call, out, nj, R, t)
        lead = out.outer_lead or (0,) * n_out
        first = tuple(call.outer_lo[d] + lead[d] for d in range(n_out))
        window = tuple(max(first[d], out.outer_hi[d]) for d in range(n_out))
        for d in range(n_out):
            tiles[d] = max(tiles[d], window[d] - first[d] + outer_sizes[d])
        row_steps = max(row_steps, blocks if R > 1
                        else block * (blocks - 1) + skew + 1)
        seats[oi] = Seat(skew, block, blocks, pad, first, window)
    extra = max([0] + [s.blocks + s.skew // R + 1 - row_steps
                       for s in seats.values() if R > 1])
    return SeatGeometry((*tiles, row_steps), extra, seats)


def _row_tileable(call: CallPlan, tile: int) -> bool:
    """Whether a grid step of ``call`` may compute several rows at once.

    Not with accumulators: folding R rows into a carried row per step
    would reorder its floating-point combines.  Not where a step reads a
    window row that a later step of the same grid step writes (a row
    recurrence), nor where a plane window's writers store rows at
    offsets that no one row tile aligns."""
    if call.accs:
        return False
    writers = call.writers()
    windows = {w.name: w for w in call.windows}
    for si, step in enumerate(call.steps):
        for rd in step.reads:
            w = windows.get(rd.src)
            if w is None or (w.plane and rd.p_off != w.p_lead):
                continue
            if not writers.get(rd.src) or writers[rd.src][-1] >= si:
                return False
    return all(len({(call.steps[si].lead - w.j_lo) % tile
                    for si in writers.get(w.name, ())}) <= 1
               for w in windows.values() if w.plane)


def row_tile(call: CallPlan, nj: int, ni: int, dtype_bytes: int) -> int:
    """Rows R that each grid step of ``call`` computes at row count
    ``nj`` and lane count ``ni``.

    1 where :func:`_row_tileable` says rows must go one at a time.
    Otherwise the largest multiple of the sublane tile that is at most
    :data:`ROW_TILE_CAP` and the grid's rows rounded up to 8, that
    starts every array input's block on a multiple of R (an input whose
    first row ``x_lo + lead - j_lo`` is positive must be such a
    multiple; one at or below 0 is brought in ahead), and whose VMEM
    need (:func:`call_vmem`) leaves the compiler's default scoped limit
    in place; the smallest such R where none does."""
    t = row_tile_unit(dtype_bytes)
    if not _row_tileable(call, t):
        return 1
    top = min(ROW_TILE_CAP, _round_up(nj + call.x_hi_off - call.x_lo,
                                      SUBLANE))
    starts = [call.x_lo + i.lead - i.j_lo for i in call.inputs
              if not i.scalar]
    fits = [R for R in range(t, top + 1, t)
            if all(s <= 0 or s % R == 0 for s in starts)]
    for R in reversed(fits):
        need = call_vmem(call, nj, ni, dtype_bytes, rows=R)
        if scoped_vmem_limit(need["total"]) is None:
            return R
    return fits[0] if fits else 1


def body_values(call: CallPlan) -> int:
    """The most values of one row each that the fused steps of ``call``
    hold at once: a liveness count over the steps' bodies, traced in
    step order.  A step holds its inputs (the rows it reads), its
    temporaries until their last use and its outputs until they are
    stored; a ``local`` row lives from the step that makes it to the
    last step that reads it.  Scalars are not counted.  Each value is
    ``(R, lanes)`` in the kernel, where the compiler keeps what the
    vector registers cannot hold in VMEM of its own.  A step whose
    function is not linked (a deserialized plan not yet re-linked)
    counts its reads and outputs."""
    return _body_values(call.steps, tuple(call.fns))


@functools.lru_cache(maxsize=256)
def _body_values(steps: tuple, fns: tuple) -> int:
    last_read: dict = {}
    for si, step in enumerate(steps):
        for rd in step.reads:
            if rd.src.startswith("local:"):
                last_read[rd.src[6:]] = si
    carried: set = set()
    peak = 0
    for si, step in enumerate(steps):
        fn = fns[step.fn_idx] if step.fn_idx < len(fns) else None
        peak = max(peak, len(carried) + _step_values(step, fn))
        for targets in step.writes:
            carried |= {str(t) for kind, t in targets if kind == "local"}
        carried = {n for n in carried if last_read.get(n, -1) > si}
    return peak


def _step_values(step: StepPlan, fn) -> int:
    """Most row values live at once inside one step's body."""
    if fn is None:
        return len(step.reads) + (step.acc is not None) + len(step.writes)
    import jax
    import jax.extend
    import jax.numpy as jnp

    row = jax.ShapeDtypeStruct((LANE,), jnp.float32)
    args = [row] * (step.acc is not None) + [
        jax.ShapeDtypeStruct((), jnp.float32) if rd.src.startswith("scalar:")
        else row for rd in step.reads]
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr

    def rows(vs):
        return {v for v in vs if not isinstance(v, jax.extend.core.Literal)
                and v.aval.ndim >= 1}

    end = len(jaxpr.eqns)
    last = {v: end for v in rows(jaxpr.outvars)}
    for ei, e in enumerate(jaxpr.eqns):
        for v in rows(e.invars):
            last[v] = max(last.get(v, -1), ei)
    live = rows(jaxpr.invars)
    peak = len(live)
    for ei, e in enumerate(jaxpr.eqns):
        live |= rows(e.outvars)
        peak = max(peak, len(live))
        live = {v for v in live if last.get(v, -1) > ei}
    return peak


def call_vmem(call: CallPlan, nj: int, ni: int, dtype_bytes: int,
              rows: Optional[int] = None) -> dict:
    """Per-buffer VMEM bytes of one call, plus their ``"total"``,
    mirroring the interpreter's allocation (``build_call``) at row tile
    ``rows`` (default :func:`row_tile`'s): rolling windows of
    ``R + stages - 1`` rows, plane windows ``p_stages`` planes with
    their row margins (:func:`row_geometry`), accumulators one padded
    row, and the two buffers of every stream block (array inputs and
    row outputs: R rows, 8 when R is 1, at most ``Nj`` for external
    outputs; accumulator outputs: 8 rows), and for R > 1 the carry
    buffer of each external output (:class:`Seat`).  Rows are padded to
    the sublane tile, lanes to 128.  ``"body"`` is what the
    step bodies hold beside them: :func:`body_values` values of R rows
    by ``Ni`` lanes."""
    ib = int(dtype_bytes)
    if rows is None:
        rows = row_tile(call, nj, ni, ib)
    geo = row_geometry(call, nj, rows, ib)

    def sub(n):
        return _pad_rows(n, ib)

    report: dict = {}
    arr_ins = [i for i in call.inputs if not i.scalar]
    for i in arr_ins:
        in_w = pad_to_lane(ni + i.i_hi - i.i_lo + i.align_pad)
        planes = i.p_stages if i.plane else 1
        report[f"in_{i.name}"] = \
            planes * sub(geo.height[f"in_{i.name}"]) * in_w * ib
        report[f"blk_{i.name}"] = 2 * sub(geo.block[i.name]) * in_w * ib
    for w in call.windows:
        width = pad_to_lane(ni + w.i_hi - w.i_lo + w.align_pad)
        planes = w.p_stages if w.plane else 1
        report[w.name] = planes * sub(geo.height[w.name]) * width * ib
    for a in call.accs:
        report[a.name] = sub(1) * pad_to_lane(ni + a.w_off) * ib
    for v in call.vloads:
        report[f"vec:{v.name}"] = \
            (v.carry + 1) * pad_to_lane(ni + v.w_off) * ib
    acc_w = {a.name: ni + a.w_off for a in call.accs}
    for o in call.outputs:
        if o.acc is not None:
            report[f"out_{o.name}"] = \
                2 * sub(SUBLANE) * pad_to_lane(acc_w[o.acc]) * ib
        elif o.kind == "external":
            _, block, _, pad = _row_seat(call, o, nj, rows, geo.tile)
            report[f"out_{o.name}"] = 2 * sub(block) * pad_to_lane(ni) * ib
            if rows > 1:
                report[f"seat_{o.name}"] = \
                    sub(pad + rows) * pad_to_lane(ni) * ib
        else:
            report[f"out_{o.name}"] = \
                2 * sub(geo.out_block) * pad_to_lane(ni) * ib
    report["body"] = body_values(call) * sub(rows) * pad_to_lane(ni) * ib
    report["total"] = sum(report.values())
    return report


def scoped_vmem_limit(need: int) -> Optional[int]:
    """The scoped VMEM limit to compile a kernel needing ``need`` bytes
    with: ``None`` (the compiler's default) while ``need`` and a quarter
    more for the compiler's own scratch fit :data:`DEFAULT_VMEM_BUDGET`,
    else that amount, capped at :data:`VMEM_CAPACITY`."""
    want = need + need // 4
    if want <= DEFAULT_VMEM_BUDGET:
        return None
    return min(want, VMEM_CAPACITY)


@dataclass(frozen=True)
class CallTiling:
    """How one call runs at concrete sizes (:func:`call_tiling`): the
    rows R each grid step computes, the buffers' rows at that R, the
    seated grid, the VMEM the call needs and the scoped VMEM limit it is
    compiled with (``None`` for the compiler's default)."""

    rows: int
    geometry: RowGeometry
    seats: SeatGeometry
    vmem_need: int
    vmem_limit: Optional[int]


def call_tiling(call: CallPlan, sizes: Sequence[int],
                dtype_bytes: int) -> CallTiling:
    """The tiling of ``call`` at ``sizes`` (``(*outer_sizes, Nj, Ni)``)
    and element size ``dtype_bytes``: :func:`row_tile`'s R, with
    :func:`row_geometry`, :func:`seat_geometry`, :func:`call_vmem`'s
    total and :func:`scoped_vmem_limit` at that R."""
    *outer_sizes, nj, ni = sizes
    R = row_tile(call, nj, ni, dtype_bytes)
    need = call_vmem(call, nj, ni, dtype_bytes, rows=R)["total"]
    return CallTiling(R, row_geometry(call, nj, R, dtype_bytes),
                      seat_geometry(call, outer_sizes, nj, R, dtype_bytes),
                      need, scoped_vmem_limit(need))
