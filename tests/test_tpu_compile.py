"""Compile the codec's device programs for a described TPU v5e, without one.

The Pallas interpreter runs the kernels everywhere else in the suite;
only the chip's own compiler refuses a tile that is not aligned, a
kernel that wants more VMEM than it may use, or a program that does not
fit HBM.  These tests lower the fused encode and decode at the largest
leaf of qwen1.5-0.5b (its 151936 x 1024 embedding) for a ``v5e:2x2``
topology and check that the compiled program holds the kernel.  The
per-coordinate DECOMPOSE draw is compiled at one full batch of lanes, to
hold its lookups' knot comparisons fused into their counts.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core.aggregate import DECOMPOSE_BATCH, AggregateGaussianMechanism
from repro.core.packing import geometry_for_bits
from repro.kernels import ops

LEAF = (151936, 1024)  # qwen1.5-0.5b tied embedding


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the
    # persistent cache but cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("percoord", [False, True],
                         ids=["scalar", "percoord"])
@pytest.mark.parametrize("bits", [8, 16, 24])
@pytest.mark.parametrize("direction", ["encode", "decode"])
def test_fused_codec_compiles_for_v5e(one_chip, direction, bits, percoord):
    geom = geometry_for_bits(bits, 4)
    leaf = _sds(LEAF, jnp.float32, one_chip)
    step = leaf if percoord else 1e-3
    if direction == "encode":
        def fn(x, s, *st):
            return ops.fused_pack_encode(x, s, st[0] if st else step, bits,
                                         geom.m_max, impl="pallas",
                                         interpret=False)

        args = (leaf, leaf) + ((leaf,) if percoord else ())
    else:
        rows = -(-LEAF[0] * LEAF[1] // (geom.group * 128))  # ops._pad_rows
        word = _sds((rows, 128), jnp.int32, one_chip)
        bias = _sds((), jnp.int32, one_chip)

        def fn(w, s, b, *st_off):
            st, off = st_off if st_off else (step, None)
            return ops.fused_unpack_decode(w, s, b, st, off, bits, LEAF,
                                           impl="pallas", interpret=False)

        args = (word, leaf, bias) + ((leaf, leaf) if percoord else ())
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [1, 4])
def test_percoord_draw_compiles_for_v5e(one_chip, n):
    """One DECOMPOSE_BATCH of per-coordinate (A, B).  Each lookup's knot
    comparisons stay fused into its counts, and the temporaries (a
    gathered 128-knot row is 512 MiB at 2^20 lanes) stay under 2 GiB."""
    mech = AggregateGaussianMechanism(n, 0.05, per_coord=True)
    key = _sds((2,), jnp.uint32, one_chip)
    compiled = jax.jit(
        lambda k: mech.global_randomness(k, (DECOMPOSE_BATCH,))
    ).lower(key).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
