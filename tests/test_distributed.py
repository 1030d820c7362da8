"""Distributed-semantics tests: run in a subprocess with 8 virtual devices
(XLA device count is locked at first jax init, so in-process is not an
option).  Each script asserts internally; the test checks exit status."""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout[-2000:] + "\n" + r.stderr[-2000:]


def test_moe_ep_matches_local_oracle():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import moe
from repro.launch import mesh as mesh_mod
cfg = moe.MoEConfig(d_model=32, d_expert=16, num_experts=8, top_k=2,
                    capacity_factor=8.0, dtype="float32")
p = moe.init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32), jnp.float32)
ref, _, _ = moe.apply_local(p, x.reshape(-1, 32), cfg)
mesh = mesh_mod.make_mesh((2, 2, 2), ("pod", "data", "model"))
with mesh:
    out, aux, disp = moe.apply_ep(p, x, cfg, mesh)
err = np.abs(np.asarray(out).reshape(-1, 32) - np.asarray(ref)).max()
assert err < 1e-4, err
g = jax.jit(jax.grad(lambda p, x: moe.apply_ep(p, x, cfg, mesh)[0].sum()))(p, x)
assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))
""")


def test_moe_tp_ragged_matches_local_oracle():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import moe
from repro.launch import mesh as mesh_mod
cfg = moe.MoEConfig(d_model=32, d_expert=16, num_experts=4, top_k=2,
                    capacity_factor=8.0, dtype="float32")
p = moe.init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32), jnp.float32)
ref, _, _ = moe.apply_local(p, x.reshape(-1, 32), cfg)
mesh = mesh_mod.make_mesh((2, 4), ("data", "model"))
with mesh:
    out, _, _ = moe.apply_sharded(p, x, cfg, mesh, data_axes=("data",))
err = np.abs(np.asarray(out).reshape(-1, 32) - np.asarray(ref)).max()
assert err < 1e-4, err
""")


def test_sharded_train_step_matches_single_device():
    """Same batch + params: sharded (2x4 mesh) loss == unsharded loss."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models.registry import build_model, make_batch
from repro.parallel import ctx as pctx, sharding as shd
from repro.launch import mesh as mesh_mod

cfg = get_config("qwen2-72b").reduced()
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
batch = make_batch(cfg, 8, 32)
loss0, _ = jax.jit(lambda p, b: model.loss(p, b))(params, batch)

mesh = mesh_mod.make_mesh((2, 4), ("data", "model"))
psh = shd.param_shardings(params, cfg, mesh)
params_s = jax.device_put(params, psh)
bsh = jax.tree.map(lambda x: NamedSharding(mesh, P(("data",))), batch)
batch_s = jax.device_put(batch, bsh)
with pctx.use_mesh(mesh, data_axes=("data",), tp_axis="model"):
    loss1, _ = jax.jit(lambda p, b: model.loss(p, b))(params_s, batch_s)
np.testing.assert_allclose(float(loss0), float(loss1), rtol=2e-2)
print("sharded loss matches:", float(loss0), float(loss1))
""")


def test_small_mesh_dryrun_lower_compile():
    """The dry-run machinery end-to-end on an in-test 4x2 mesh."""
    _run("""
import jax, jax.numpy as jnp
from repro.configs import get_config, SHAPES
from repro.core import roofline
from repro.launch import specs as specs_mod
from repro.models.registry import build_model
from repro.optim import adamw
from repro.parallel import ctx as pctx
from repro.launch import mesh as mesh_mod
from repro.train import step as train_mod
import dataclasses

cfg = get_config("granite-moe-1b-a400m").reduced()
cfg = dataclasses.replace(cfg, dtype="bfloat16")
model = build_model(cfg)
mesh = mesh_mod.make_mesh((4, 2), ("data", "model"))
with pctx.use_mesh(mesh, data_axes=("data",), tp_axis="model"):
    tcfg = train_mod.TrainConfig(accum_steps=2)
    step = train_mod.make_train_step(model, tcfg, adamw.AdamWConfig())
    state_sds, state_sh = specs_mod.state_specs(model, mesh)
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32,
                                     sharding=NamedSharding(mesh, P(("data",))))
             for k in ("tokens", "labels")}
    lowered = jax.jit(step, in_shardings=(state_sh, jax.tree.map(
        lambda s: s.sharding, batch)), donate_argnums=(0,)).lower(state_sds, batch)
    compiled = lowered.compile()
terms = roofline.from_compiled(compiled, arch="granite-reduced",
                               shape="tiny", mesh_name="4x2", chips=8,
                               model_flops=1e9)
assert terms.hlo_flops > 0 and terms.compute_s > 0
print("dryrun small mesh ok:", terms.dominant)
""")
