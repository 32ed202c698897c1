"""Integration tests: training loop (with/without compression),
checkpoint save/restore/elastic-reshard, FL rounds with stragglers, and
a miniature multi-device dry-run in a subprocess."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint import checkpoint
from repro.data import synthetic
from repro.dist import meshctx
from repro.dist.compress import CompressionConfig
from repro.fl.federated import FederatedAveraging, FLConfig
from repro.train import steps


def _train(cfg, tc, n_steps=25, seed=0):
    mesh = meshctx.default_mesh()
    meshctx.set_mesh(mesh)
    state = steps.init_train_state(cfg, tc, jax.random.PRNGKey(seed))
    step = jax.jit(steps.build_train_step(cfg, tc, mesh))
    dc = synthetic.DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4)
    losses = []
    for i in range(n_steps):
        batch = synthetic.with_frontend_stubs(synthetic.lm_batch(dc, i), cfg)
        state, m = step(state, batch, jnp.int32(i))
        losses.append(float(m["loss"]))
    return state, losses


@pytest.mark.parametrize(
    "mechanism", ["none_", "aggregate_gaussian", "irwin_hall", "layered_shifted"]
)
def test_training_loss_decreases_with_compression(mechanism):
    cfg = configs.get_smoke_config("qwen1.5-0.5b").scaled(compute_dtype="float32")
    comp = None
    if mechanism != "none_":
        comp = CompressionConfig(mechanism=mechanism, sigma=5e-4, clip=0.5)
    tc = steps.TrainConfig(optimizer="adamw", lr=5e-3, grad_accum=2, compression=comp)
    _, losses = _train(cfg, tc, n_steps=30)
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses


def test_checkpoint_resume_exact(tmp_path):
    cfg = configs.get_smoke_config("minitron-4b").scaled(compute_dtype="float32")
    tc = steps.TrainConfig(optimizer="adamw", lr=1e-3, grad_accum=1)
    mesh = meshctx.default_mesh()
    meshctx.set_mesh(mesh)
    state = steps.init_train_state(cfg, tc, jax.random.PRNGKey(1))
    step = jax.jit(steps.build_train_step(cfg, tc, mesh))
    dc = synthetic.DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4)
    for i in range(3):
        state, _ = step(state, synthetic.lm_batch(dc, i), jnp.int32(i))
    checkpoint.save(str(tmp_path), 3, state)
    assert checkpoint.latest_step(str(tmp_path)) == 3
    restored = checkpoint.restore(str(tmp_path), 3, state)
    # continue both for 2 steps -> identical results (deterministic data)
    s_a, s_b = state, restored
    for i in range(3, 5):
        batch = synthetic.lm_batch(dc, i)
        s_a, ma = step(s_a, batch, jnp.int32(i))
        s_b, mb = step(s_b, batch, jnp.int32(i))
        assert float(ma["loss"]) == pytest.approx(float(mb["loss"]), abs=1e-6)
    for a, b in zip(jax.tree.leaves(s_a["params"]), jax.tree.leaves(s_b["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore onto a different 'mesh' (here: different sharding tree) —
    elastic scaling path; values must be preserved exactly."""
    cfg = configs.get_smoke_config("rwkv6-1.6b").scaled(compute_dtype="float32")
    tc = steps.TrainConfig(optimizer="sgd", lr=1e-3)
    meshctx.set_mesh(meshctx.default_mesh())
    state = steps.init_train_state(cfg, tc, jax.random.PRNGKey(2))
    checkpoint.save(str(tmp_path), 0, state)
    shardings = steps.train_state_shardings(cfg, tc, meshctx.default_mesh())
    restored = checkpoint.restore(str(tmp_path), 0, state, shardings)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_federated_rounds_with_stragglers():
    """FL runtime: quadratic objective, straggler dropout, compressed
    aggregation — converges to the true mean."""
    d = 32
    rng = np.random.default_rng(0)
    targets = jnp.asarray(rng.normal(size=(16, d)), jnp.float32)

    def client_grad(params, cid, rnd):
        return {"w": params["w"] - targets[cid]}

    cfg = FLConfig(
        n_clients=16, mechanism="aggregate_gaussian", sigma=1e-3, clip=2.0,
        cohort_fraction=0.8, straggler_fraction=0.2, lr=0.5,
    )
    fl = FederatedAveraging(cfg, client_grad)
    params = {"w": jnp.zeros(d)}
    for r in range(40):
        params, info = fl.round(params, r)
    err = float(jnp.linalg.norm(params["w"] - targets.mean(0)))
    # cohort subsampling leaves residual error ~ cohort-mean jitter
    assert err < 1.0, err
    assert info["bits_per_coord"] < 32


def test_multidevice_compressed_training_subprocess():
    """8 fake devices, 2x2x2 (pod,data,model) mesh: compressed cross-pod
    aggregation trains and matches the homomorphic psum path."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.dist import meshctx
from repro.dist.compress import CompressionConfig
from repro.data import synthetic
from repro.train import steps

mesh = meshctx.make_mesh((2, 2, 2))
meshctx.set_mesh(mesh)
cfg = configs.get_smoke_config("qwen3-32b").scaled(compute_dtype="float32")
comp = CompressionConfig(mechanism="aggregate_gaussian", sigma=5e-4, clip=0.5,
                         msg_dtype="int32")
tc = steps.TrainConfig(optimizer="adamw", lr=5e-3, grad_accum=2, compression=comp)
state = steps.init_train_state(cfg, tc, jax.random.PRNGKey(0))
state_sh = steps.train_state_shardings(cfg, tc, mesh)
state = jax.device_put(state, state_sh)
step = jax.jit(steps.build_train_step(cfg, tc, mesh))
dc = synthetic.DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8)
losses = []
for i in range(20):
    batch = synthetic.lm_batch(dc, i)
    state, m = step(state, batch, jnp.int32(i))
    losses.append(float(m["loss"]))
assert np.isfinite(losses).all(), losses
assert np.mean(losses[-4:]) < np.mean(losses[:4]) - 0.2, losses
print("SUBPROCESS_OK", losses[0], losses[-1])
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), ".."), env=env, timeout=900,
    )
    assert "SUBPROCESS_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


def test_dryrun_mini_subprocess():
    """dryrun machinery on an 8-device production-mesh analogue."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax
from repro.dist import meshctx
from repro.launch import dryrun
mesh = meshctx.make_mesh((2, 2, 2))
fn, args, sh = dryrun.build_cell("qwen1.5-0.5b", "decode_32k", mesh)
compiled = jax.jit(fn, in_shardings=sh).lower(*args).compile()
mem = compiled.memory_analysis()
coll, counts = dryrun.collective_bytes(compiled.as_text())
assert sum(counts.values()) > 0
print("DRYRUN_OK", mem.temp_size_in_bytes, sum(coll.values()))
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), ".."), env=env, timeout=900,
    )
    assert "DRYRUN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


def test_spmd_compression_noise_is_exact_gaussian():
    """The systems-integration core property: the cross-pod compressed
    aggregate (shard_map + int psum + seeded dither recompute) has error
    EXACTLY N(0, sigma^2) against the true mean — KS-tested on 8 fake
    devices with a (4-pod, 2-model) mesh."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, math; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.dist import meshctx
from repro.dist.compress import CompressionConfig, compress_tree

mesh = meshctx.make_mesh((8,), ("pod",))
n, d, sigma = 8, 40_000, 0.25
cfg = CompressionConfig(mechanism="aggregate_gaussian", sigma=sigma, clip=4.0,
                        msg_dtype="int32")
gs = jax.random.uniform(jax.random.PRNGKey(0), (n, d), minval=-3, maxval=3)

def agg(per_pod_grads, seed):
    def inner(g):
        return compress_tree({"g": g[0]}, cfg, jax.random.PRNGKey(seed),
                             axis="pod", n_clients=n)["g"]
    return jax.shard_map(inner, mesh=mesh, in_specs=P("pod"),
                         out_specs=P(), check_vma=False)(per_pod_grads)

errs = []
for s in range(6):
    y = agg(gs, s)
    errs.append(np.asarray(y - gs.mean(0)))
err = np.concatenate(errs) / sigma
srt = np.sort(err); m = len(srt)
cdf = 0.5 * (1 + np.vectorize(math.erf)(srt / math.sqrt(2)))
ks = max(np.max(np.abs(cdf - np.arange(1, m + 1) / m)),
         np.max(np.abs(cdf - np.arange(m) / m)))
assert ks < 1.95 / math.sqrt(m), ks
assert abs(err.std() - 1.0) < 0.01, err.std()
print("KS_OK", ks)
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), ".."), env=env, timeout=900,
    )
    assert "KS_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


def test_moe_expert_parallel_matches_tensor_parallel():
    """EP (all_to_all dispatch) and TP (d_ff-sharded) MoE paths compute
    identical outputs, including e_loc > 1 (4 experts on 2 model shards)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro import configs
from repro.dist import meshctx
from repro.models import moe, nn
cfg = configs.get_smoke_config("dbrx-132b").scaled(compute_dtype="float32")
for mesh_shape in [(1, 4), (2, 2)]:
    mesh = meshctx.make_mesh(mesh_shape, ("data", "model"))
    meshctx.set_mesh(mesh)
    params = {"moe": nn.init_params(moe.moe_specs(cfg), jax.random.PRNGKey(0))}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model)) * 0.5
    y_tp = moe.moe_block(cfg, params, x)
    y_ep = moe.moe_block(cfg.scaled(moe_ep=True), params, x)
    assert jnp.allclose(y_tp, y_ep, atol=2e-4), (
        mesh_shape, float(jnp.max(jnp.abs(y_tp - y_ep))))
print("EP_TP_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), ".."), env=env, timeout=900,
    )
    assert "EP_TP_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
