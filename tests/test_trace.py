"""Spans and counters (repro.trace): recording off and on, nesting per
thread, self time, counters, the engine's compile spans, and a span's
copy in the profiler's trace."""
import gzip
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.core import clear_compile_cache, compile_program
from repro.core.programs import heat3d_program


@pytest.fixture
def fresh_cache():
    clear_compile_cache()
    yield
    clear_compile_cache()


def test_recording_off_records_nothing():
    with trace.span("test.off", a=1) as s:
        trace.count("test.counter")
    assert s.end_ns >= s.start_ns > 0
    with trace.recording() as rec:
        pass
    with trace.span("test.after"):
        trace.count("test.counter")
    assert rec.spans == [] and not rec.counters


def test_nesting_parent_ids_and_self_time():
    with trace.recording() as rec:
        with trace.span("test.outer", k="v") as outer:
            with trace.span("test.inner"):
                time.sleep(0.01)
            with trace.span("test.inner"):
                with trace.span("test.inner"):
                    time.sleep(0.002)
            time.sleep(0.005)
        with trace.span("test.second"):
            pass
    by_name = {}
    for s in rec.spans:
        by_name.setdefault(s.name, []).append(s)
    (o,) = by_name["test.outer"]
    assert o.parent is None and o.attrs == {"k": "v"}
    assert (o.start_ns, o.end_ns) == (outer.start_ns, outer.end_ns)
    assert [r.name for r in rec.roots()] == ["test.outer", "test.second"]
    kids = rec.children(o)
    assert [k.name for k in kids] == ["test.inner", "test.inner"]
    assert all(k.parent == o.id for k in kids)
    (deep,) = rec.children(kids[1])
    assert deep.parent == kids[1].id and rec.children(deep) == []
    assert o.start_ns <= kids[0].start_ns and kids[1].end_ns <= o.end_ns
    assert rec.self_s(o) == pytest.approx(
        o.seconds - kids[0].seconds - kids[1].seconds)
    assert rec.self_s(o) >= 0.004
    # a span nested in one of its own name counts once in the total
    assert rec.total_s("test.inner") == pytest.approx(kids[0].seconds + kids[1].seconds)
    assert len({s.id for s in rec.spans}) == len(rec.spans) == 5


def test_spans_nest_per_thread():
    """A thread's spans nest under that thread's open span, never under
    another thread's."""
    ready, go = threading.Event(), threading.Event()

    def worker():
        with trace.span("test.worker"):
            ready.set()
            go.wait(10)
            with trace.span("test.worker.step"):
                pass

    with trace.recording() as rec:
        with trace.span("test.main"):
            t = threading.Thread(target=worker)
            t.start()
            ready.wait(10)
            with trace.span("test.main.step"):
                go.set()
                t.join(10)
    spans = {s.name: s for s in rec.spans}
    assert spans["test.worker"].parent is None
    assert spans["test.worker.step"].parent == spans["test.worker"].id
    assert spans["test.main.step"].parent == spans["test.main"].id
    assert {s.name for s in rec.roots()} == {"test.main", "test.worker"}


def test_counters_and_jax_cache_events():
    with trace.recording() as rec:
        trace.count("test.hits")
        trace.count("test.hits", 4)
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert rec.counters == {"test.hits": 5, "jax.cache_hits": 1,
                            "jax.cache_misses": 2}


def test_a_recording_inside_another_restores_it():
    with trace.recording() as outer:
        with trace.span("test.a"):
            with trace.recording() as inner:
                with trace.span("test.b"):
                    trace.count("test.n")
        trace.count("test.n")
    assert [s.name for s in outer.spans] == ["test.a"]
    assert [s.name for s in inner.spans] == ["test.b"]
    assert inner.spans[0].parent is None
    assert outer.counters == {"test.n": 1} and inner.counters == {"test.n": 1}


def test_add_records_an_interval_measured_before(fresh_cache):
    t0 = time.perf_counter_ns()
    with trace.recording() as rec:
        s = rec.add("test.before", t0, t0 + 2_000_000, note="x")
    assert rec.spans == [s] and s.seconds == pytest.approx(0.002)
    assert s.parent is None and s.attrs == {"note": "x"}


def test_compile_program_spans_and_cache_counters(fresh_cache):
    prog = heat3d_program()
    with trace.recording() as rec:
        gen = compile_program(prog, dim_sizes={"Nk": 5, "Nj": 11, "Ni": 132})
        compile_program(prog, dim_sizes={"Nk": 5, "Nj": 11, "Ni": 132})
    first, second = rec.roots()
    assert first.name == second.name == "hfav.compile_program"
    assert first.attrs == {"program": "heat3d", "backend": "auto"}
    names = [c.name for c in rec.children(first)]
    for phase in ("hfav.infer", "hfav.dataflow", "hfav.fusion", "hfav.storage",
                  "hfav.plan_pallas", "hfav.autoprobe", "hfav.plancheck", "hfav.emit"):
        assert phase in names
    assert names.index("hfav.infer") < names.index("hfav.fusion") < names.index("hfav.emit")
    assert rec.children(second) == []
    assert rec.counters["hfav.compile_cache.miss"] == 1
    assert rec.counters["hfav.compile_cache.hit"] == 1
    assert rec.counters["hfav.plan_cache.miss"] == 1

    u = jnp.asarray(np.random.default_rng(0).standard_normal((5, 11, 132)), jnp.float32)
    step = jax.jit(lambda a: gen.fn(u=a))
    with trace.recording() as rec:
        jax.block_until_ready(step(u))
        jax.block_until_ready(step(u))
    # the kernel is built while JAX traces, once; the compiled calls run
    # no span.  One grid step per k plane of the 5x11x132 field: its 11
    # rows fit one 16-row tile; one more step after the last plane
    # writes that plane's goal rows, which each step writes one step
    # late.  The VMEM model's need at that tile (plancheck.call_vmem:
    # the 3-plane window, two stream blocks of each of input and output,
    # the output's 24-row carry buffer, 8 body values of 16 x 256) stays
    # under the default scoped limit, so no limit is passed (0).  The
    # one output is a goal, seated by the kernel.
    (build,) = rec.named("hfav.build_call")
    need = 73728 + 2 * 32768 + 24 * 256 * 4 + 8 * 16 * 256 * 4
    assert build.attrs == {"call": "heat3d_n0", "grid_steps": 5 * 1 + 1,
                           "row_tile": 16, "vmem_need_bytes": need,
                           "vmem_limit_bytes": 0}
    assert rec.counters["hfav.grid_steps"] == 5 * 1 + 1
    assert rec.counters["hfav.row_tile"] == 16
    assert rec.counters["hfav.vmem_need_bytes"] == need == 294912
    assert rec.counters["hfav.vmem_limit_bytes"] == 0
    assert rec.counters["hfav.outputs_seated"] == 1
    assert rec.counters["hfav.outputs_trimmed"] == 0


def test_plan_disk_spans_and_hits(fresh_cache, tmp_path):
    prog = heat3d_program()
    with trace.recording() as rec:
        compile_program(prog, plan_cache_dir=tmp_path)
        clear_compile_cache()
        compile_program(prog, plan_cache_dir=tmp_path)
    ops = [s.attrs["op"] for s in rec.named("hfav.plan_disk")]
    assert ops == ["get", "put", "get"]
    assert rec.counters["hfav.plan_disk.hit"] == 1
    # the warm compile skipped the analysis pipeline
    assert len(rec.named("hfav.infer")) == 1


def test_span_agrees_with_its_copy_in_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with trace.recording() as rec:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            # a thread's first event under a new profiler session pays
            # the profiler's own set-up on its way out
            with trace.span("test.warmup"):
                pass
            with trace.span("test.profiled"):
                time.sleep(0.02)
        finally:
            jax.profiler.stop_trace()
    (xplane,) = Path(tmp_path).glob("plugins/profile/*/*.xplane.pb")
    raw = xplane.read_bytes()
    if raw[:2] == b"\x1f\x8b":
        raw = gzip.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    copies = [ev.duration_ns for pl in data.planes for ln in pl.lines for ev in ln.events
              if ev.name == "test.profiled"]
    (recorded,) = rec.named("test.profiled")
    assert len(copies) == 1
    assert abs(copies[0] - (recorded.end_ns - recorded.start_ns)) <= 50_000
