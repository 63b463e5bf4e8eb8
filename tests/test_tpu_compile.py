"""Compile the Pallas stencil kernels for a described TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests refuse what Mosaic would refuse
on the chip (block shapes off the (8, 128) tile, unaligned DMA slices,
unsupported primitives, VMEM over the scoped limit) without one.  They
compile with ``interpret=False`` at Ni=1024 and check that each jitted
program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture: only the test
worker that runs this file loads the TPU library.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import trace
from repro.core import compile_batched, compile_program, dimorder, tiling
from repro.core.programs import ALL_PROGRAMS

#: (program, {size symbol: int}, cap of R): the main path's programs at
#: Ni=1024, R rows and one row a grid step, on a 2-D and a 3-D grid
CASES = [
    pytest.param(name, sizes, cap, id=f"{name}-{'one_row' if cap else 'rows'}")
    for name, sizes, cap in [
        ("laplace5", {"Nj": 1024, "Ni": 1024}, None),
        ("cosmo", {"Nk": 8, "Nj": 256, "Ni": 1024}, None),
        ("heat3d", {"Nk": 8, "Nj": 256, "Ni": 1024}, None),
        ("normalization", {"Nj": 1024, "Ni": 1024}, None),
        ("hydro1d", {"Nj": 1024, "Ni": 1024}, None),
        ("plane_sum", {"Nk": 8, "Nj": 256, "Ni": 1024}, None),
        # one row a step, rows off the tile: the last 8-row block is partial
        ("laplace5", {"Nj": 1020, "Ni": 1024}, 1),
        ("heat3d", {"Nk": 8, "Nj": 254, "Ni": 1024}, 1),
        # the second nest streams a 1023-wide intermediate
        ("normalization", {"Nj": 1020, "Ni": 1024}, 1),
    ]
] + [
    pytest.param(name, sizes, None, id=tag)
    for tag, name, sizes in [
        # the chip benchmark's two cells at their real shapes, as
        # R-row tiles (cosmo's 774 rows end in a partial row block)
        ("cosmo1-80x774x1158", "cosmo", {"Nk": 80, "Nj": 774, "Ni": 1158}),
        ("heat3d-512cubed", "heat3d", {"Nk": 512, "Nj": 512, "Ni": 512}),
        # rows not a multiple of the row tile, and rows fewer than it
        ("laplace5-rows-ragged", "laplace5", {"Nj": 1020, "Ni": 1024}),
        ("heat3d-rows-below-tile", "heat3d", {"Nk": 8, "Nj": 12, "Ni": 1024}),
        # a producer plane window stored R rows at a time
        ("heat3d_stage-rows", "heat3d_stage",
         {"Nk": 8, "Nj": 256, "Ni": 1024}),
        # a body whose own values outgrow the buffers: without counting
        # them the VMEM model chose R = 72 here, and Mosaic refused the
        # kernel (an 18.67 MiB scoped allocation over the 16 MiB limit)
        ("hydro2d-1028sq", "hydro2d", {"Nj": 1028, "Ni": 1028}),
        # NPB MG class C's cell: box reads out of a plane window beside a
        # second input, two carried accumulators, one row a step, and
        # 514 lanes (4 * 128 + 2)
        ("npb-mg-c-514cubed", "mg_resid", {"Nk": 514, "Nj": 514, "Ni": 514}),
    ]
]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _inputs(prog, sizes, sharding, batch=()):
    """Shape-only inputs from the program's axiom extents."""
    out = {}
    for ax in prog.axioms:
        exts = [ax.extents[d.rstrip("?")] for d in ax.term.ref.dims]
        shape = tuple(sizes[e.size] + e.hi - e.lo for e in exts)
        out[ax.term.ref.name] = jax.ShapeDtypeStruct(
            batch + shape, jnp.float32, sharding=sharding)
    return out


@pytest.mark.parametrize("name,sizes,cap", CASES)
def test_kernel_compiles_for_v5e(one_chip, name, sizes, cap, monkeypatch):
    if cap is not None:
        monkeypatch.setattr(tiling, "ROW_TILE_CAP", cap)
    prog = ALL_PROGRAMS[name]()
    gen = compile_program(prog, backend="pallas", interpret=False,
                          use_cache=False)
    compiled = jax.jit(lambda a: gen.fn(**a)).lower(
        _inputs(prog, sizes, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_batched_kernel_compiles_for_v5e(one_chip):
    """The serving path: the kernel vmapped over a batch of requests."""
    prog = ALL_PROGRAMS["heat3d"]()
    sizes = {"Nk": 8, "Nj": 256, "Ni": 1024}
    bgen = compile_batched(prog, "pallas", jit=False, interpret=False,
                           dim_sizes=sizes, use_cache=False)
    compiled = jax.jit(bgen.fn).lower(
        _inputs(prog, sizes, one_chip, batch=(4,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: The sweep cells' chain link at the cells' sizes: cosmo1's field, and a
#: Hydro2D subdomain whose 8196 rows end in a partial 8-row block.
CHAIN = [
    pytest.param("cosmo", {"Nk": 80, "Nj": 774, "Ni": 1158}, id="cosmo1-80x774x1158"),
    pytest.param("hydro2d", {"Nj": 8196, "Ni": 8196}, id="hydro2d-8196sq"),
]

_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+) ([\w-]+)\((.*)$")


@pytest.fixture
def chip_layouts(one_chip, monkeypatch):
    """The host half follows the described chip's default layouts, as
    it follows the attached chip's on a TPU host."""
    device, = one_chip.device_set
    monkeypatch.setattr(dimorder, "layout_device", lambda: device)


@pytest.mark.parametrize("name,sizes", CHAIN)
def test_donated_chain_link_holds_no_glue_for_v5e(one_chip, chip_layouts,
                                                  name, sizes):
    """One link of the benchmark's sweep chain, built as
    ``bench/generators/sweep.py`` ``_chained`` builds it (the previous
    output donated, one element returned as a marker), compiles to the
    kernel alone: no ``pad`` or ``dynamic-update-slice``, no ``slice``
    but the one-element marker, and no ``copy``.  The device keeps
    cosmo1's ``f32[80,774,1158]`` with the 774-row dim major (no row
    padding), so the host half runs cosmo's nest with ``j`` outer and
    hands the kernel transposed views, which are bitcasts; a nest in the
    array's own order took one layout-changing ``copy`` in and one
    out."""
    prog = ALL_PROGRAMS[name]()
    gen = compile_program(prog, backend="pallas", interpret=False, use_cache=False)
    inputs = _inputs(prog, sizes, one_chip)

    def link(arrays, _previous):
        out = gen.fn(**arrays)
        leaf = jax.tree.leaves(out)[0]
        return out, leaf[tuple(n // 2 for n in leaf.shape)]

    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda a: gen.fn(**a), inputs))
    text = jax.jit(link, donate_argnums=1, keep_unused=True).lower(
        inputs, shapes).compile().as_text()
    assert "tpu_custom_call" in text
    for line in text.splitlines():
        m = _OP.match(line)
        if not m:
            continue
        _, rtype, opcode, _ = m.groups()
        assert opcode not in ("pad", "dynamic-update-slice"), line
        assert not opcode.startswith("copy"), line
        if opcode == "slice":
            dims = re.match(r"\w+\[([\d,]*)\]", rtype).group(1)
            assert math.prod(int(d) for d in dims.split(",") if d) == 1, line


def test_permuted_cosmo1_call_compiles_for_v5e(one_chip, chip_layouts):
    """cosmo1's nest in the device's dim order: ``j`` outer, the 80 k
    rows one row tile of R = 80, 774 planes and one more grid step."""
    prog = ALL_PROGRAMS["cosmo"]()
    gen = compile_program(prog, backend="pallas", interpret=False,
                          use_cache=False)
    sizes = {"Nk": 80, "Nj": 774, "Ni": 1158}
    with trace.recording() as rec:
        compiled = jax.jit(lambda a: gen.fn(**a)).lower(
            _inputs(prog, sizes, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert rec.counters["hfav.nest_permuted"] == 1
    assert rec.counters["hfav.row_tile"] == 80
    assert rec.counters["hfav.grid_steps"] == 775
