"""Distributed-path tests on 8 fake CPU devices (subprocess: the main test
process must keep its 1-device view).

Covers the shard_map paths that only activate under a mesh: the EP MoE
dispatcher, the shard-local embedding gather/scatter, int8 error-feedback
gradient all-reduce, and w8a16 serving weights.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.models import lm
from repro.parallel.sharding import make_rules

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> str:
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n"
        f"import sys; sys.path.insert(0, {os.path.join(ROOT, 'src')!r})\n"
        + body
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=420, cwd=ROOT,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_ep_moe_matches_fallback():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import smoke_config
from repro.models import mlp as M
from repro.models.common import init_params
from repro.models.mlp import moe_defs
from repro.parallel.sharding import make_rules
from jax.sharding import PartitionSpec as P, NamedSharding

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
cfg = dataclasses.replace(smoke_config("olmoe-1b-7b"), n_experts=8, top_k=2,
                          capacity_factor=8.0)
rules_ep = make_rules(with_pod=False, batch_axes=("data",), mesh=mesh)
rules_ref = make_rules(with_pod=False, batch_axes=None)
params = init_params(moe_defs(cfg), jax.random.PRNGKey(0))
x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 16, cfg.d_model)), jnp.float32)
ref_out, ref_aux = M.moe_fwd(params, x, cfg, rules_ref)
with jax.set_mesh(mesh):
    ep_out, ep_aux = jax.jit(lambda p, xx: M.moe_fwd(p, xx, cfg, rules_ep))(
        params, jax.device_put(x, NamedSharding(mesh, P("data"))))
err = float(jnp.abs(ep_out - ref_out).max())
assert err < 1e-4, err
def loss_ep(p):
    o, a = M.moe_fwd(p, x, cfg, rules_ep); return jnp.sum(o**2) + a
def loss_ref(p):
    o, a = M.moe_fwd(p, x, cfg, rules_ref); return jnp.sum(o**2) + a
with jax.set_mesh(mesh):
    g1 = jax.jit(jax.grad(loss_ep))(params)
g2 = jax.grad(loss_ref)(params)
gerr = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
    lambda a, b: float(jnp.abs(a - b).max()), g1, g2)))
assert gerr < 1e-4, gerr
print("EP_OK", err, gerr)
""")
    assert "EP_OK" in out


def test_sharded_embedding_gather_matches_take():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.models.lm import _embed_lookup
from repro.parallel.sharding import make_rules
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
rules = make_rules(with_pod=False, batch_axes=("data",), mesh=mesh)
rng = np.random.default_rng(0)
table = jnp.asarray(rng.normal(size=(128, 64)), jnp.float32)
ids = jnp.asarray(rng.integers(0, 128, (4, 8)), jnp.int32)
with jax.set_mesh(mesh):
    got = jax.jit(lambda t, i: _embed_lookup(t, i, rules, jnp.float32))(table, ids)
np.testing.assert_allclose(np.asarray(got), np.asarray(table)[np.asarray(ids)],
                           rtol=1e-5)
# gradient wrt table: scatter-add semantics
def loss(t):
    return jnp.sum(_embed_lookup(t, ids, rules, jnp.float32) ** 2)
with jax.set_mesh(mesh):
    g = jax.jit(jax.grad(loss))(table)
expect = np.zeros_like(np.asarray(table))
np.add.at(expect, np.asarray(ids).ravel(),
          2 * np.asarray(table)[np.asarray(ids)].reshape(-1, 64))
np.testing.assert_allclose(np.asarray(g), expect, rtol=1e-4, atol=1e-4)
print("EMB_OK")
""")
    assert "EMB_OK" in out


def test_int8_psum_error_feedback():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import compression
mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
N = 1000
xs = jnp.asarray(rng.normal(size=(8, N)).astype(np.float32))
err = jnp.zeros((8, N), jnp.float32)
def f(x, e):
    o, ne = compression.int8_psum(x[0], "data", e[0])
    return o[None], ne[None]
fm = jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                   out_specs=(P("data"), P("data")), check_vma=False)
true = np.asarray(xs).sum(0)
acc = np.zeros(N)
for it in range(20):
    out, err = jax.jit(fm)(xs, err)
    acc += np.asarray(out)[0]
r0 = np.abs(np.asarray(out)[0] - true).max() / np.abs(true).max()
r20 = np.abs(acc / 20 - true).max() / np.abs(true).max()
assert r0 < 0.05 and r20 < r0, (r0, r20)   # EF mean converges
print("COMP_OK", r0, r20)
""")
    assert "COMP_OK" in out


def test_w8a16_quantized_forward_close():
    cfg = smoke_config("qwen1.5-32b")
    rules = make_rules(with_pod=False, batch_axes=None)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    qparams = lm.quantize_mlp_weights(params, cfg)
    # structure: MLP leaves became {'q','scale'} with int8 payload
    leaf = qparams["layers"]["mlp"]["w_up"]
    assert leaf["q"].dtype == jnp.int8
    assert leaf["scale"].shape[-2] == 1
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (2, 16)))
    cache = lm.init_cache(cfg, 2, 16)
    l1, _ = lm.prefill(params, {"tokens": tokens}, cache, cfg, rules)
    cache = lm.init_cache(cfg, 2, 16)
    l2, _ = lm.prefill(qparams, {"tokens": tokens}, cache, cfg, rules)
    assert float(jnp.abs(l1 - l2).max()) < 0.1


def test_compressed_train_step_runs():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import smoke_config
from repro.models import lm
from repro.optim import OptimizerConfig, make_optimizer
from repro.train import make_compressed_train_step
mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
cfg = dataclasses.replace(smoke_config("yi-6b"), shard_kv_heads=False)
opt = make_optimizer(OptimizerConfig(lr=1e-2, warmup_steps=2, total_steps=50))
params = lm.init_model(cfg, jax.random.PRNGKey(0))
state = opt.init(params)
step = make_compressed_train_step(cfg, opt, mesh, dp_axes=("data",))
err_fb = step.init_err_fb(params)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32))),
         "targets": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32))),
         "mask": jnp.ones((8, 32))}
with jax.set_mesh(mesh):
    jstep = jax.jit(step)
    losses = []
    for i in range(6):
        params, state, err_fb, metrics = jstep(params, state, batch, i, err_fb)
        losses.append(float(metrics["loss"]))
assert losses[-1] < losses[0], losses   # learns through int8 gradients
print("CSTEP_OK", losses[0], losses[-1])
""")
    assert "CSTEP_OK" in out


def test_elastic_rescale_across_mesh_sizes():
    """Checkpoint written under an 8-device mesh restores onto a 4-device
    mesh (simulated node loss) with identical values — the elastic path."""
    out = _run("""
import tempfile, numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.checkpoint import CheckpointManager
from repro.runtime import reshard_state

d8 = jax.devices()[:8]
d4 = jax.devices()[:4]
mesh8 = jax.sharding.Mesh(np.array(d8).reshape(4, 2), ("data", "model"))
mesh4 = jax.sharding.Mesh(np.array(d4).reshape(2, 2), ("data", "model"))

tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((16,))}
sh8 = {"w": NamedSharding(mesh8, P("data", "model")),
       "b": NamedSharding(mesh8, P("data"))}
state8 = jax.tree_util.tree_map(jax.device_put, tree, sh8)

with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d)
    mgr.save(1, state8)
    sh4 = {"w": NamedSharding(mesh4, P("data", "model")),
           "b": NamedSharding(mesh4, P("data"))}
    state4 = mgr.restore(1, sh4)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(state4[k]), np.asarray(tree[k]))
        assert state4[k].sharding.mesh.shape["data"] == 2
    # and the in-memory reshard path (no disk)
    state4b = reshard_state(state8, sh4)
    np.testing.assert_array_equal(np.asarray(state4b["w"]), np.asarray(tree["w"]))
print("ELASTIC_OK")
""")
    assert "ELASTIC_OK" in out
