"""Unit tests for the repro.dist subsystem: compression round-trips,
bit accounting, and sharding-rule resolution."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.dist import meshctx, sharding
from repro.dist.compress import CompressionConfig, compress_tree, message_bits
from repro.models import registry


# ------------------------------------------------------------- compress
@pytest.mark.parametrize(
    "mechanism",
    ["aggregate_gaussian", "aggregate_laplace", "irwin_hall",
     "layered_shifted", "layered_direct"],
)
def test_compress_tree_roundtrip_unbiased_exact_std(mechanism):
    """Point-to-point (n=1): the decompressed tree is the input plus
    zero-mean noise with std exactly sigma."""
    sigma = 0.05
    comp = CompressionConfig(mechanism=mechanism, sigma=sigma, clip=1.0)
    x = {
        "a": jax.random.normal(jax.random.PRNGKey(1), (40_000,)) * 0.1,
        "b": {"c": jax.random.normal(jax.random.PRNGKey(2), (64, 8)) * 0.1},
    }
    y = compress_tree(x, comp, jax.random.PRNGKey(3))
    err = np.concatenate(
        [np.asarray(a - b).ravel() for a, b in zip(jax.tree.leaves(y), jax.tree.leaves(x))]
    )
    d = err.size
    assert abs(err.mean()) < 4 * sigma / math.sqrt(d)
    assert abs(err.std() - sigma) < 0.03 * sigma


def test_compress_tree_none_is_identity_after_clip():
    comp = CompressionConfig(mechanism="none_", sigma=0.0, clip=0.25)
    x = {"w": jnp.asarray([-1.0, -0.1, 0.0, 0.1, 1.0])}
    y = compress_tree(x, comp, jax.random.PRNGKey(0))
    np.testing.assert_allclose(
        np.asarray(y["w"]), [-0.25, -0.1, 0.0, 0.1, 0.25], atol=1e-7
    )


def test_compress_tree_preserves_structure_and_dtype():
    comp = CompressionConfig(mechanism="aggregate_gaussian", sigma=1e-3, clip=1.0)
    x = {"a": jnp.zeros((4, 4), jnp.bfloat16), "b": jnp.zeros((8,), jnp.float32)}
    y = compress_tree(x, comp, jax.random.PRNGKey(0))
    assert jax.tree.structure(y) == jax.tree.structure(x)
    assert y["a"].dtype == jnp.bfloat16 and y["b"].dtype == jnp.float32


def test_compress_tree_homomorphic_psum_matches_mean():
    """Across a real pod axis the homomorphic mechanisms return the
    cross-client mean up to the mechanism's noise scale."""
    n, d, sigma = 8, 4096, 1e-3
    mesh = meshctx.make_mesh((8, 1, 1))
    xs = jax.random.uniform(jax.random.PRNGKey(0), (n, d), minval=-0.5, maxval=0.5)
    for mechanism in ["aggregate_gaussian", "aggregate_laplace",
                      "irwin_hall", "layered_shifted"]:
        comp = CompressionConfig(mechanism=mechanism, sigma=sigma, clip=1.0)

        def agg(g):
            return compress_tree(
                {"g": g[0]}, comp, jax.random.PRNGKey(7), axis="pod", n_clients=n
            )["g"]

        y = jax.shard_map(
            agg, mesh=mesh, in_specs=P("pod"), out_specs=P(), check_vma=False
        )(xs)
        err = np.asarray(y - xs.mean(0))
        # loose mean bound: a missing decode offset would bias by ~step/2
        # (= O(sigma)), an order of magnitude above this threshold
        assert abs(err.mean()) < 10 * sigma / math.sqrt(d), mechanism
        assert abs(err.std() - sigma) < 0.1 * sigma, (mechanism, err.std())


def test_unknown_mechanism_rejected():
    with pytest.raises(KeyError):
        CompressionConfig(mechanism="quantum_teleport")


# --------------------------------------------------------- bit accounting
@pytest.mark.parametrize(
    "mechanism", ["aggregate_gaussian", "irwin_hall", "layered_shifted"]
)
def test_message_bits_monotone_in_sigma(mechanism):
    """Coarser noise -> bigger quantization step -> fewer bits."""
    bits = [
        message_bits(CompressionConfig(mechanism=mechanism, sigma=s, clip=1.0), 4)
        for s in (1e-3, 1e-2, 1e-1)
    ]
    assert bits[0] >= bits[1] >= bits[2], bits
    assert bits[0] > bits[2], bits
    assert all(b < 32.0 for b in bits), bits


def test_message_bits_none_is_float32():
    assert message_bits(CompressionConfig(mechanism="none_", sigma=0.0), 4) == 32.0


# ------------------------------------------------------------- sharding
def _mesh222():
    return meshctx.make_mesh((2, 2, 2))


def test_param_rules_dense_vs_ep_moe():
    """EP_PARAM_RULES shard the expert dim over 'model' with full d_ff;
    PARAM_RULES tensor-shard d_ff and leave experts replicated."""
    mesh = _mesh222()
    cfg = configs.get_smoke_config("dbrx-132b")
    pspecs = registry.param_specs(cfg)
    dense = sharding.param_shardings(pspecs, mesh, sharding.PARAM_RULES)
    ep = sharding.param_shardings(pspecs, mesh, sharding.EP_PARAM_RULES)
    # stacked MoE weight: (layers, expert, embed, mlp)
    w_dense = dense["layers"]["moe"]["w_gate"].spec
    w_ep = ep["layers"]["moe"]["w_gate"].spec
    assert w_dense == P(None, None, "data", "model")
    assert w_ep == P(None, "model", "data", None)


def test_no_fsdp_rules_drop_data_axis():
    mesh = _mesh222()
    cfg = configs.get_smoke_config("qwen1.5-0.5b")
    pspecs = registry.param_specs(cfg)
    sh = sharding.param_shardings(pspecs, mesh, sharding.NO_FSDP_RULES)
    for ns in jax.tree.leaves(sh):
        flat = [a for part in ns.spec if part for a in
                ((part,) if isinstance(part, str) else part)]
        assert "data" not in flat and "pod" not in flat, ns.spec


def test_spec_resolution_skips_nondivisible_and_reused_axes():
    mesh = _mesh222()
    # dim 0 not divisible by data (2): stays replicated
    s = sharding.spec_for_axes(("embed", "mlp"), (3, 8), mesh, sharding.PARAM_RULES)
    assert s == P(None, "model")
    # same logical axis twice: the mesh axis is applied only once
    s = sharding.spec_for_axes(("embed", "embed"), (8, 8), mesh, sharding.PARAM_RULES)
    assert s == P("data", None)


def test_batch_spec_divisibility():
    mesh = _mesh222()
    assert sharding.batch_spec(mesh, 2, 8)[0] == ("pod", "data")
    assert sharding.batch_spec(mesh, 2, 2)[0] == "pod"
    assert sharding.batch_spec(mesh, 2, 3)[0] is None
    assert sharding.batch_spec(mesh, 3, 8) == P(("pod", "data"), None, None)


def test_manual_axes_filtered_from_batch_axes():
    mesh = _mesh222()
    assert meshctx.batch_axes(mesh, 8) == ("pod", "data")
    with meshctx.manual_axes({"pod"}):
        assert meshctx.batch_axes(mesh, 8) == ("data",)
    assert meshctx.batch_axes(mesh, 8) == ("pod", "data")


def test_default_mesh_has_pod_axis_and_all_devices():
    mesh = meshctx.default_mesh()
    assert mesh.axis_names == ("pod", "data", "model")
    assert math.prod(mesh.devices.shape) == len(jax.devices())
    if len(jax.devices()) >= 8:
        assert mesh.shape["pod"] > 1
