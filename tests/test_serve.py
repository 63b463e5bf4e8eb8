"""PlanServe: shape bucketing, pad/unpad exactness, the micro-batcher,
the compiled-bucket table, and the batched-execution contract
(compile_batched bit-identical to per-example compile_program on every
backend)."""
import time

import numpy as np
import pytest

from repro.core import (clear_compile_cache, compile_batched,
                        compile_program, registered_interpreters)
from repro.core.programs import (energy3d_program, heat3d_program,
                                 laplace5_program, row_sum_program)
from repro.serve.plans import (DEFAULT_QUANTUM, VMAP_SAFE, PlanServe,
                               bucket_sizes, is_reduction, pad_to_bucket,
                               quantize, request_sizes, unpad_outputs)


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_compile_cache()
    yield
    clear_compile_cache()


def _rng():
    return np.random.default_rng(7)


def _laplace_ref(u, backend="interp_jax"):
    gen = compile_program(laplace5_program(), backend=backend)
    return np.asarray(gen.fn(cell=u)["lap"])


# ---------------------------------------------------------------------------
# Buckets and padding
# ---------------------------------------------------------------------------

def test_quantize():
    assert quantize(1, 32) == 32
    assert quantize(32, 32) == 32
    assert quantize(33, 32) == 64
    assert quantize(9, 1) == 9
    with pytest.raises(ValueError):
        quantize(0, 32)
    with pytest.raises(ValueError):
        quantize(5, 0)


def test_request_sizes_and_validation():
    prog = laplace5_program()
    u = np.zeros((9, 17), np.float32)
    assert request_sizes(prog, {"cell": u}) == {"Nj": 9, "Ni": 17}
    with pytest.raises(ValueError, match="expects input arrays"):
        request_sizes(prog, {})
    with pytest.raises(ValueError, match="rank"):
        request_sizes(prog, {"cell": np.zeros((9,), np.float32)})


def test_bucket_key_is_canonical():
    prog = laplace5_program()
    b = bucket_sizes(prog, {"Nj": 9, "Ni": 17}, 8)
    assert b == (("Ni", 24), ("Nj", 16))


def test_reduction_detection():
    assert not is_reduction(laplace5_program())
    assert is_reduction(energy3d_program())
    assert is_reduction(row_sum_program())


def test_pad_unpad_roundtrip_is_bit_identical():
    """The serving exactness contract: pad to a bucket, run the padded
    shape, re-seat — bit-identical to the unpadded run (goal stores
    seat only the valid region; the padded lanes never feed it)."""
    prog = laplace5_program()
    u = _rng().standard_normal((9, 17)).astype(np.float32)
    sizes = request_sizes(prog, {"cell": u})
    bucket = bucket_sizes(prog, sizes, DEFAULT_QUANTUM)
    padded = pad_to_bucket(prog, {"cell": u}, bucket)
    assert padded["cell"].shape == (32, 32)
    gen = compile_program(prog, backend="interp_jax")
    out_padded = {k: np.asarray(v)
                  for k, v in gen.fn(**padded).items()}
    out = unpad_outputs(prog, out_padded, sizes)
    np.testing.assert_array_equal(out["lap"], _laplace_ref(u))


def test_pad_unpad_roundtrip_heat3d():
    prog = heat3d_program()
    u = _rng().standard_normal((5, 9, 17)).astype(np.float32)
    sizes = request_sizes(prog, {"u": u})
    assert sizes == {"Nk": 5, "Nj": 9, "Ni": 17}
    bucket = bucket_sizes(prog, sizes, 8)
    padded = pad_to_bucket(prog, {"u": u}, bucket)
    gen = compile_program(prog, backend="interp_jax")
    out = unpad_outputs(prog, {k: np.asarray(v)
                               for k, v in gen.fn(**padded).items()}, sizes)
    ref = np.asarray(compile_program(prog, backend="interp_jax")
                     .fn(u=u)["heat"])
    np.testing.assert_array_equal(out["heat"], ref)


# ---------------------------------------------------------------------------
# compile_batched: the vmap contract, every backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend",
                         sorted({"jax"} | set(registered_interpreters())))
def test_compile_batched_matches_per_example(backend):
    """vmap-safety pin: the batched executor is bit-identical to running
    each example through the unbatched artifact — for the legacy JAX
    emitter and every registered plan interpreter (this is what lets
    PlanServe accept the backends in VMAP_SAFE)."""
    prog = laplace5_program()
    rng = _rng()
    batch = np.stack([rng.standard_normal((9, 17)).astype(np.float32)
                      for _ in range(3)])
    bgen = compile_batched(prog, backend)
    outs = {k: np.asarray(v)
            for k, v in bgen.fn({"cell": batch}).items()}
    gen = compile_program(prog, backend)
    for i in range(3):
        ref = np.asarray(gen.fn(cell=batch[i])["lap"])
        np.testing.assert_array_equal(outs["lap"][i], ref)


def test_vmap_safe_backends_are_available():
    """Every backend PlanServe claims vmap-safe must actually exist —
    the registry (or the legacy jax emitter) must know it."""
    assert VMAP_SAFE <= {"jax"} | set(registered_interpreters())


# ---------------------------------------------------------------------------
# The serving engine
# ---------------------------------------------------------------------------

def test_serve_single_request_bit_identical():
    u = _rng().standard_normal((9, 17)).astype(np.float32)
    with PlanServe({"laplace5": laplace5_program()},
                   max_wait_ms=1.0) as srv:
        out = srv.serve("laplace5", {"cell": u})
    np.testing.assert_array_equal(out["lap"], _laplace_ref(u))


def test_batch_assembly_and_scatter_order():
    """max_batch same-bucket requests coalesce into one batch, and each
    ticket gets *its own* request's outputs back (distinct inputs pin
    the scatter order)."""
    rng = _rng()
    inputs = [rng.standard_normal((9, 17)).astype(np.float32)
              for _ in range(4)]
    with PlanServe({"laplace5": laplace5_program()}, max_batch=4,
                   max_wait_ms=200.0) as srv:
        srv.prefill("laplace5", {"Nj": 9, "Ni": 17}, batch=4)
        tickets = [srv.submit("laplace5", {"cell": u}) for u in inputs]
        outs = [t.result(60) for t in tickets]
    for u, out, t in zip(inputs, outs, tickets):
        np.testing.assert_array_equal(out["lap"], _laplace_ref(u))
        assert t.stats["batch_size"] == 4
    snap = srv.metrics.snapshot()
    assert snap["requests"] == 4
    assert snap["batches"] == 1
    assert snap["batch_size"]["max"] == 4


def test_max_wait_flushes_partial_batch():
    """A lone request must not wait for a full batch: the batcher
    flushes it once max_wait_ms expires."""
    u = _rng().standard_normal((9, 17)).astype(np.float32)
    with PlanServe({"laplace5": laplace5_program()}, max_batch=16,
                   max_wait_ms=30.0) as srv:
        t = srv.submit("laplace5", {"cell": u})
        out = t.result(60)
    np.testing.assert_array_equal(out["lap"], _laplace_ref(u))
    assert t.stats["batch_size"] == 1
    # it did hold the request for the batching window
    assert t.stats["queue_wait_ms"] >= 20.0


def test_mixed_sizes_land_in_distinct_buckets():
    rng = _rng()
    a = rng.standard_normal((9, 17)).astype(np.float32)    # -> (32, 32)
    b = rng.standard_normal((40, 40)).astype(np.float32)   # -> (64, 64)
    with PlanServe({"laplace5": laplace5_program()},
                   max_wait_ms=1.0) as srv:
        out_a = srv.serve("laplace5", {"cell": a})
        out_b = srv.serve("laplace5", {"cell": b})
        snap = srv.metrics.snapshot()
    np.testing.assert_array_equal(out_a["lap"], _laplace_ref(a))
    np.testing.assert_array_equal(out_b["lap"], _laplace_ref(b))
    assert snap["compiles"]["count"] == 2
    assert len(snap["buckets"]) == 2


def test_bucket_compiles_once_across_requests():
    rng = _rng()
    with PlanServe({"laplace5": laplace5_program()},
                   max_wait_ms=1.0) as srv:
        for _ in range(5):
            # different sizes, same bucket
            n = int(rng.integers(5, 30))
            srv.serve("laplace5",
                      {"cell": rng.standard_normal((n, n))
                       .astype(np.float32)})
        snap = srv.metrics.snapshot()
    assert snap["requests"] == 5
    assert snap["compiles"]["count"] == 1


def test_reduction_is_served_exactly():
    """Reductions bucket exactly (quantum 1): zero-padding would change
    the reduce-tree shape, so PlanServe must not pad them."""
    u = _rng().standard_normal((4, 7, 20)).astype(np.float32)
    with PlanServe({"energy3d": energy3d_program()},
                   max_wait_ms=1.0) as srv:
        out = srv.serve("energy3d", {"u": u})
    ref = np.asarray(compile_program(energy3d_program(),
                                     backend="interp_jax").fn(u=u)["energy"])
    np.testing.assert_array_equal(out["energy"], ref)


def test_multiple_programs_one_engine():
    rng = _rng()
    u2 = rng.standard_normal((9, 17)).astype(np.float32)
    u3 = rng.standard_normal((5, 9, 17)).astype(np.float32)
    with PlanServe({"laplace5": laplace5_program(),
                    "heat3d": heat3d_program()}, max_wait_ms=1.0) as srv:
        ta = srv.submit("laplace5", {"cell": u2})
        tb = srv.submit("heat3d", {"u": u3})
        out_a, out_b = ta.result(60), tb.result(60)
    np.testing.assert_array_equal(out_a["lap"], _laplace_ref(u2))
    ref = np.asarray(compile_program(heat3d_program(),
                                     backend="interp_jax").fn(u=u3)["heat"])
    np.testing.assert_array_equal(out_b["heat"], ref)


def test_metrics_snapshot_schema():
    u = _rng().standard_normal((9, 17)).astype(np.float32)
    with PlanServe({"laplace5": laplace5_program()},
                   max_wait_ms=1.0) as srv:
        srv.serve("laplace5", {"cell": u})
        snap = srv.metrics.snapshot()
    assert snap["requests"] == 1
    assert snap["requests_per_s"] > 0
    for dist in (snap["latency_ms"], snap["queue_wait_ms"]):
        assert set(dist) == {"p50", "p99", "mean", "max"}
        assert dist["p50"] <= dist["p99"] <= dist["max"] or dist["max"] == 0
    assert set(snap["compiles"]) == {"count", "disk_hits", "total_ms"}
    assert snap["batch_size"]["max"] == 1


def test_engine_rejects_bad_configuration():
    with pytest.raises(ValueError, match="vmap-safe"):
        PlanServe({"laplace5": laplace5_program()}, backend="auto")
    prog = laplace5_program()
    prog.goals[0].store_as = None
    with pytest.raises(ValueError, match="store_as"):
        PlanServe({"laplace5": prog})


def test_unknown_program_and_closed_engine():
    srv = PlanServe({"laplace5": laplace5_program()}, max_wait_ms=1.0)
    with pytest.raises(ValueError, match="unknown program"):
        srv.submit("nope", {})
    srv.close()
    srv.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit("laplace5", {"cell": np.zeros((4, 4), np.float32)})


def test_close_drains_queued_requests():
    """close() must not strand in-flight tickets: everything already
    queued still executes before the batcher exits."""
    rng = _rng()
    srv = PlanServe({"laplace5": laplace5_program()}, max_batch=2,
                    max_wait_ms=500.0)
    inputs = [rng.standard_normal((9, 17)).astype(np.float32)
              for _ in range(3)]
    tickets = [srv.submit("laplace5", {"cell": u}) for u in inputs]
    t0 = time.perf_counter()
    srv.close()
    assert time.perf_counter() - t0 < 60
    for u, t in zip(inputs, tickets):
        np.testing.assert_array_equal(t.result(1)["lap"], _laplace_ref(u))


def test_batch_spans_carry_the_ticket_batch_id():
    """One batch's spans carry its tickets' ``batch_id``; its steps (pad,
    stack, call, fetch, unpad) cover nearly all of it, and a ticket's
    service time is the batch span's start to the fetch's end."""
    from repro import trace

    rng = _rng()
    inputs = [rng.standard_normal((200, 300)).astype(np.float32)
              for _ in range(4)]
    prog = laplace5_program()
    with trace.recording() as rec:
        with PlanServe({"laplace5": prog}, max_batch=4,
                       max_wait_ms=500.0) as srv:
            srv.prefill("laplace5", request_sizes(prog, {"cell": inputs[0]}),
                        batch=4)
            tickets = [srv.submit("laplace5", {"cell": u}) for u in inputs]
            for t in tickets:
                t.result(60)
    assert [s.attrs["program"] for s in rec.named("hfav.serve.compile")] \
        == ["laplace5"]
    (batch,) = rec.named("hfav.serve.batch")
    assert {t.stats["batch_id"] for t in tickets} == {batch.attrs["batch_id"]}
    assert batch.attrs["n"] == batch.attrs["slots"] == 4
    steps = rec.children(batch)
    assert [s.name for s in steps] == [
        "hfav.serve.pad", "hfav.serve.stack", "hfav.serve.call",
        "hfav.serve.fetch", "hfav.serve.unpad"]
    assert all(s.attrs == {"batch_id": batch.attrs["batch_id"]} for s in steps)
    assert sum(s.seconds for s in steps) >= 0.95 * batch.seconds
    fetch = steps[3]
    for t in tickets:
        service_ms = t.stats["latency_ms"] - t.stats["queue_wait_ms"]
        assert service_ms == pytest.approx((fetch.end_ns - batch.start_ns) / 1e6,
                                           abs=1e-3)
    # the batcher thread waited for the batch to fill, outside the batch
    assert rec.named("hfav.serve.collect")
    assert all(s.parent is None for s in rec.named("hfav.serve.collect"))
