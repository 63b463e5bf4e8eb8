"""The trace reduction, on synthetic events and on a small trace that
the sweep generator recorded on a TPU v5e (heat3d at 16x64x256)."""
from pathlib import Path

import pytest

from bench import trace as tr

FIXTURE = Path(__file__).parent / "fixtures" / "heat3d_small_sweep.xplane.pb.gz"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, start, dur):
    return tr.Event(plane, line, name, float(start), float(dur))


def test_op_name_keeps_the_instruction_name():
    assert tr.op_name("%hfav_cosmo_n0.1 = f32[80,776,1158]{2,1,0} custom-call(%u)") \
        == "hfav_cosmo_n0.1"
    assert tr.op_name("%pad.4 = f32[8]{0} pad(%slice.2, %c)") == "pad.4"
    assert tr.op_name("copy.3") == "copy.3"


def test_reduce_on_synthetic_events():
    events = [
        _ev(HOST, "main", tr.WINDOW, 100, 1000),
        _ev(HOST, "main", "bench.dispatch", 150, 100),
        _ev(HOST, "main", "bench.block", 600, 300),
        _ev(HOST, "pool", "Transpose::Execute", 600, 200),
        _ev(HOST, "pool", "Transpose::Execute", 1000, 60),
        # a kernel, overlapping glue, a gap, glue half outside the window
        _ev(DEV, tr.OPS_LINE, "%hfav_k.1 = f32[] custom-call()", 100, 300),
        _ev(DEV, tr.OPS_LINE, "%copy.2 = f32[] copy()", 350, 100),
        _ev(DEV, tr.OPS_LINE, "%vmap_hfav_k.1 = f32[] custom-call()", 900, 120),
        _ev(DEV, tr.OPS_LINE, "%pad.4 = f32[] pad()", 1050, 200),
        # not an op line, and an op before the window: neither counts
        _ev(DEV, "XLA Modules", "jit_step", 100, 1000),
        _ev(DEV, tr.OPS_LINE, "%early = f32[] copy()", 0, 50),
    ]
    s = tr.reduce(events)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((350 + 120 + 50) * 1e-9)   # [100,450) [900,1020) [1050,1100)
    assert s.kernel_s == pytest.approx(420e-9)
    assert s.glue_s == pytest.approx(150e-9)
    assert s.devices == 1
    assert list(s.ops) == ["hfav_k.1", "vmap_hfav_k.1", "copy.2", "pad.4"]
    # the longest gap [450, 900) is covered most by bench.block (300 ns),
    # more than by the runtime's transpose (150 ns of it)
    assert s.gaps[0] == ("bench.block", pytest.approx(450e-9))
    # the gap [1020, 1050) only by the transpose: a host event by its name
    assert s.gaps[1][0] == "host:Transpose::Execute"
    assert [round(g * 1e9) for _, g in s.gaps] == [450, 30]
    b = tr.breakdown(s)
    assert [k for k, _ in b["device_ops"]] == ["hfav_k.1", "vmap_hfav_k.1", "copy.2", "pad.4"]
    assert b["idle_gaps"] == [[name, g] for name, g in s.gaps]


def test_reduce_needs_exactly_one_window():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce([_ev(DEV, tr.OPS_LINE, "%a = f32[] copy()", 0, 5)])


def test_reduce_on_a_trace_recorded_on_the_chip():
    events = tr.load(FIXTURE)
    assert {e.plane for e in events} >= {DEV, HOST}
    s = tr.reduce(events)
    assert s.devices == 1
    # 55 sweeps of a 16x64x256 heat3d in a 20.6 ms window: one kernel and
    # two glue ops (pad, slice) per sweep, dispatch-bound at this size
    assert s.window_s == pytest.approx(0.020590243)
    assert s.busy_s == pytest.approx(0.007536716)
    assert s.kernel_s == pytest.approx(0.007432002)
    assert s.glue_s == pytest.approx(0.000104714)
    assert list(s.ops) == ["hfav_heat3d_n0.1", "pad.4", "slice.29"]
    assert s.busy_s <= s.kernel_s + s.glue_s <= s.window_s
    assert len(s.gaps) == 10
    assert s.gaps[0] == ("bench.block", pytest.approx(0.001934436))
    # dispatch-bound: the device waits on the host's dispatch and blocks
    assert [name for name, _ in s.gaps[:3]] == ["bench.block", "bench.dispatch", "bench.block"]
    assert "unattributed" not in {name for name, _ in s.gaps}
    b = tr.breakdown(s)
    assert b["device_ops"][0] == ["hfav_heat3d_n0.1", pytest.approx(0.007432002)]
    assert len(b["idle_gaps"]) == 10
