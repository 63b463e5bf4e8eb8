"""Multi-process serving: spawned PlanServe workers sharing one on-disk
plan cache — the first worker fills it, later workers (and later cold
starts) compile warm, and every worker's outputs stay bit-identical to
the in-process reference."""
import numpy as np
import pytest

from repro.core import clear_compile_cache, compile_program
from repro.core.programs import laplace5_program

pytestmark = pytest.mark.slow


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_compile_cache()
    yield
    clear_compile_cache()


def test_workers_share_one_plan_cache(tmp_path):
    from repro.serve.workers import ServeWorker, WorkerPool

    rng = np.random.default_rng(3)
    u = rng.standard_normal((9, 17)).astype(np.float32)
    ref = np.asarray(compile_program(laplace5_program(),
                                     backend="interp_jax").fn(cell=u)["lap"])

    # cold worker: plans from scratch and persists the plan
    with ServeWorker(["laplace5"], cache_dir=tmp_path,
                     max_wait_ms=1.0) as w:
        np.testing.assert_array_equal(
            w.serve("laplace5", {"cell": u})["lap"], ref)
        cold = w.metrics()
    assert cold["compiles"]["count"] == 1
    assert cold["compiles"]["disk_hits"] == 0
    assert len(list(tmp_path.glob("*.json"))) == 1

    # a warm pool: every worker finds the plan on disk
    with WorkerPool(2, ["laplace5"], cache_dir=tmp_path,
                    max_wait_ms=1.0) as pool:
        for _ in range(4):
            np.testing.assert_array_equal(
                pool.serve("laplace5", {"cell": u})["lap"], ref)
        snaps = pool.close()
    assert len(snaps) == 2
    for snap in snaps:
        assert snap["requests"] == 2  # round-robin split the 4 requests
        assert snap["compiles"]["disk_hits"] == snap["compiles"]["count"] == 1


def test_worker_survives_bad_requests(tmp_path):
    from repro.serve.workers import ServeWorker

    u = np.random.default_rng(5).standard_normal((9, 17)).astype(np.float32)
    with ServeWorker(["laplace5"], cache_dir=tmp_path,
                     max_wait_ms=1.0) as w:
        with pytest.raises(RuntimeError, match="unknown program"):
            w.serve("nope", {})
        with pytest.raises(RuntimeError, match="expects input arrays"):
            w.serve("laplace5", {})
        # the worker still serves after failed requests
        out = w.serve("laplace5", {"cell": u})
    ref = np.asarray(compile_program(laplace5_program(),
                                     backend="interp_jax").fn(cell=u)["lap"])
    np.testing.assert_array_equal(out["lap"], ref)


def test_pool_refuses_second_worker_on_tpu_host(monkeypatch):
    """A worker that opened a TPU holds every chip of its host: a pool
    of two is refused before the second worker starts, and the first is
    stopped."""
    from repro.serve import workers

    started = []

    class FakeTpuWorker:
        platform, device_count = "tpu", 1

        def __init__(self, program_names, **kwargs):
            started.append(self)
            self.closed = False

        def close(self):
            self.closed = True

    monkeypatch.setattr(workers, "ServeWorker", FakeTpuWorker)
    with pytest.raises(RuntimeError, match="holds all 1 chip"):
        workers.WorkerPool(2, ["laplace5"])
    assert len(started) == 1 and started[0].closed


def test_worker_reports_its_devices(tmp_path):
    from repro.serve.workers import ServeWorker

    with ServeWorker(["laplace5"], cache_dir=tmp_path) as w:
        assert (w.platform, w.device_count) == ("cpu", 1)
