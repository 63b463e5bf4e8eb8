"""Distribution tests that need multiple (host) devices run in a
subprocess with XLA_FLAGS set before jax import: pipeline parallelism
correctness and a small end-to-end dry-run cell (lower+compile on the
production mesh + roofline record)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def test_pipeline_parallel_matches_sequential():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import pipeline_apply
from jax.sharding import AxisType
mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
L, D = 8, 16
rng = np.random.default_rng(0)
params = {"w": jnp.asarray(rng.standard_normal((L, D, D)) * 0.2, jnp.float32),
          "b": jnp.asarray(rng.standard_normal((L, D)) * 0.1, jnp.float32)}
x = jnp.asarray(rng.standard_normal((12, D)), jnp.float32)
def block(bp, h):
    return jnp.tanh(h @ bp["w"] + bp["b"])
ref = x
for l in range(L):
    ref = block(jax.tree.map(lambda a: a[l], params), ref)
out = pipeline_apply(block, params, x, mesh, "stage", n_micro=6)
assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
print("PIPELINE_OK")
"""
    r = _run(code)
    assert "PIPELINE_OK" in r.stdout, r.stderr[-2000:]


def test_sharded_train_step_on_host_mesh():
    """train_step under pjit with FSDPxTP shardings on a 4-device mesh
    must equal the unsharded single-device step."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import ARCHS, smoke
from repro.models import init_params
from repro.optim.adamw import AdamWCfg, init_opt_state
from repro.train.step import make_train_step
from repro.distributed.sharding import param_specs, shardings_of
from repro.distributed.ctx import use_mesh

cfg = smoke(ARCHS["minitron-4b"])
params = init_params(jax.random.PRNGKey(0), cfg)
opt = init_opt_state(params)
rngn = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rngn.integers(0, cfg.vocab, (4, 16)), jnp.int32)}
batch["targets"] = batch["tokens"]
ocfg = AdamWCfg(lr=1e-3, warmup_steps=1, total_steps=10)
step = make_train_step(cfg, ocfg)
p_ref, _, m_ref = jax.jit(step)(params, opt, batch)

from jax.sharding import AxisType
mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
with use_mesh(mesh):
    pshard = shardings_of(param_specs(params, mesh), mesh)
    oshard = {"m": pshard, "v": pshard, "step": NamedSharding(mesh, P())}
    bshard = {k: NamedSharding(mesh, P(("data",), None)) for k in batch}
    jstep = jax.jit(step, in_shardings=(pshard, oshard, bshard))
    p_sh, _, m_sh = jstep(params, opt, batch)
np.testing.assert_allclose(float(m_ref["loss"]), float(m_sh["loss"]), rtol=1e-5)
for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=1e-3)
print("SHARDED_OK")
"""
    r = _run(code, devices=4)
    assert "SHARDED_OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.slow
def test_dryrun_cell_end_to_end(tmp_path):
    """One full dry-run cell on the 16x16 production mesh: lower, compile,
    memory_analysis, roofline record.  ~160s of XLA compile; marked slow
    so scripts/test_fast.sh can skip it."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "mamba2-130m", "--shape", "decode_32k",
         "--out", str(tmp_path)],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        cwd=REPO, capture_output=True, text=True, timeout=560,
    )
    assert "dry-run complete: 1 ok" in r.stdout, r.stdout + r.stderr[-2000:]
    rec = json.load(open(tmp_path / "mamba2-130m__decode_32k__16x16.json"))
    assert rec["status"] == "ok"
    assert rec["flops_per_device"] > 0
    assert rec["bottleneck"] in ("compute", "memory", "collective")
