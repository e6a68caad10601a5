"""Distributed integration tests.

These need >1 XLA device, so they run in subprocesses with
``--xla_force_host_platform_device_count`` (the main test process keeps the
single real CPU device for the smoke tests)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)


def test_small_mesh_train_step_runs():
    """Real sharded execution (not just compile) on a 2x4 fake-device mesh."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke
from repro.launch.mesh import make_test_mesh
from repro.runtime import Trainer, TrainerConfig

mesh = make_test_mesh((2, 4), ("data", "model"))
cfg = get_smoke("qwen2-1.5b")
t = Trainer(cfg, TrainerConfig(seq_len=64, global_batch=4, total_steps=10), mesh=mesh)
out = t.run(4)
assert len(out["losses"]) == 4
assert all(np.isfinite(l) for l in out["losses"])
print("OK", out["losses"][-1])
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_small_mesh_multipod_axes():
    """3-axis (pod, data, model) mesh lowers + compiles a train step."""
    code = """
import jax, jax.numpy as jnp
from repro.configs import get_smoke
from repro.launch.mesh import make_test_mesh
from repro.distributed.sharding import make_plan
from repro.optim import make_optimizer
from repro.runtime import TrainState, make_train_step
from repro.runtime.trainstep import state_specs
from repro.models import init_params, input_specs
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_smoke("yi-9b")
plan = make_plan(mesh, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads)
opt = make_optimizer("adamw")
def init_state():
    p = init_params(cfg, jax.random.PRNGKey(0))
    return TrainState(p, opt.init(p), jnp.zeros((), jnp.int32))
shape = jax.eval_shape(init_state)
specs = state_specs(cfg, plan, shape)
sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                  is_leaf=lambda x: isinstance(x, P))
sds = jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                   shape, sh)
batch = input_specs(cfg, 64, 8, "train", plan)
fn = make_train_step(cfg, plan, opt)
with mesh:
    compiled = jax.jit(fn, donate_argnums=0, out_shardings=(sh, None)).lower(sds, batch).compile()
txt = compiled.as_text()
assert any(op in txt for op in ("all-reduce", "all-gather")), "no collectives emitted"
print("OK collectives present")
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_elastic_resize_resharding():
    """Train on a 4-device mesh, checkpoint, resize to 2 devices, resume."""
    code = """
import tempfile, numpy as np
from repro.configs import get_smoke
from repro.launch.mesh import make_test_mesh
from repro.runtime import Trainer, TrainerConfig

cfg = get_smoke("phi4-mini-3.8b")
with tempfile.TemporaryDirectory() as d:
    mesh4 = make_test_mesh((2, 2), ("data", "model"))
    t = Trainer(cfg, TrainerConfig(seq_len=32, global_batch=4, total_steps=20,
                                   ckpt_dir=d, ckpt_every=2), mesh=mesh4)
    t.run(4)
    loss_before = t.run(1)["losses"][0]
    # node failure: shrink to a 2-device mesh and reload the checkpoint
    mesh2 = make_test_mesh((1, 2), ("data", "model"))
    t.resize(mesh2)
    assert int(t.state.step) >= 2
    out = t.run(2)
    assert all(np.isfinite(l) for l in out["losses"])
    print("OK resized+resumed at step", out["final_step"])
"""
    r = _run(code)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_sharded_equals_single_device():
    """The sharded loss on a 2x2 mesh matches the unsharded loss."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke
from repro.launch.mesh import make_test_mesh
from repro.distributed.sharding import make_plan
from repro.models import init_params, loss_fn
from repro.data import make_batch

cfg = get_smoke("gemma3-4b")
params = init_params(cfg, jax.random.PRNGKey(0))
raw = make_batch(cfg, 64, 4, seed=0)
batch = {k: jnp.asarray(v) for k, v in raw.items()}
plan0 = make_plan(None, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads)
l0 = float(jax.jit(lambda p, b: loss_fn(cfg, plan0, p, b))(params, batch))
mesh = make_test_mesh((2, 2), ("data", "model"))
plan1 = make_plan(mesh, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads)
with mesh:
    l1 = float(jax.jit(lambda p, b: loss_fn(cfg, plan1, p, b))(params, batch))
assert abs(l0 - l1) < 5e-3 * max(1.0, abs(l0)), (l0, l1)
print("OK", l0, l1)
"""
    r = _run(code, devices=4)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_gradient_compression_roundtrip():
    """Error-feedback int8 compression: compressed DP psum approximates the
    exact mean and the error feedback shrinks the bias over steps."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.optim.compress import compressed_psum_tree

mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
P = jax.sharding.PartitionSpec
def f(g, e):
    return compressed_psum_tree(g, e, "data")
gs = {"w": jnp.arange(32.0).reshape(4, 8) / 7.3}
out = jax.jit(jax.shard_map(f, mesh=mesh,
                            in_specs=({"w": P("data")}, {"w": P("data")}),
                            out_specs=({"w": P()}, {"w": P("data")}),
                            check_vma=False))(gs, {"w": jnp.zeros((4, 8))})
red = np.asarray(out[0]["w"])  # (1, 8): sum over the 4 device shards
exact = np.asarray(gs["w"].sum(axis=0, keepdims=True))
rel = float(np.max(np.abs(red - exact)) / (np.max(np.abs(exact)) + 1e-9))
assert rel < 0.05, rel
# error feedback captured the quantization residual
assert float(np.max(np.abs(np.asarray(out[1]["w"])))) < 0.02
print("OK rel", rel)
"""
    r = _run(code, devices=4)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
