"""The program's named scopes reach the compiled HLO's ``op_name``
metadata, from which a device trace names its operations: ``fl.forward``
(and, under ``value_and_grad``, the backward as
``transpose(jvp(fl.forward))`` with the remat recompute inside it),
``fl.optimizer`` and ``fl.codec/<part>``."""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.dist import meshctx
from repro.dist.compress import CompressionConfig, compress_tree
from repro.train import steps

INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = .*?\s?([a-z][a-z0-9-]*)\(")
OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def _comp(per_coord):
    return CompressionConfig(mechanism="aggregate_gaussian", sigma=0.05,
                             clip=1.0, per_coord=per_coord, fused=True,
                             msg_bits=16)


def _step(pod):
    cfg = configs.get_smoke_config("starcoder2-3b").scaled(
        compute_dtype="float32")
    if pod:
        mesh = meshctx.make_mesh((2, 1, 1), ("pod", "data", "model"),
                                 devices=jax.devices()[:2])
    else:
        mesh = meshctx.make_mesh((1, 1), ("data", "model"),
                                 devices=jax.devices()[:1])
    tc = steps.TrainConfig(optimizer="adamw", lr=3e-4,
                           compression=_comp(per_coord=False))
    abstract = steps.make_train_state_specs(cfg, tc)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, steps.train_state_shardings(cfg, tc, mesh))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32)}
    saved = meshctx._mesh
    meshctx.set_mesh(mesh)
    try:
        fn = jax.jit(steps.build_train_step(cfg, tc, mesh), donate_argnums=0)
        return fn.lower(state, batch, jnp.int32(0)).compile()
    finally:
        meshctx.set_mesh(saved)


def _codec():
    comp = _comp(per_coord=True)
    tree = {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32),
            "b": jax.ShapeDtypeStruct((96,), jnp.float32)}
    fn = jax.jit(lambda v, k: compress_tree(v, comp, k))
    return fn.lower(tree, jax.random.PRNGKey(0)).compile()


BUILD = {"step": lambda: _step(pod=False), "pod_step": lambda: _step(pod=True),
         "codec": _codec}


@functools.lru_cache(maxsize=None)
def instructions(program):
    """(opcode, op_name) of every instruction of the compiled program."""
    out = []
    for line in BUILD[program]().as_text().splitlines():
        m = INSTRUCTION.match(line)
        if m:
            name = OP_NAME.search(line)
            out.append((m.group(1), name.group(1) if name else ""))
    return out


SCOPES = {
    "forward": lambda n: "fl.forward" in n and "transpose(" not in n,
    "backward": lambda n: "transpose(jvp(fl.forward))" in n,
    "remat": lambda n: ("transpose(jvp(fl.forward))" in n
                        and "rematted_computation" in n),
    "optimizer": lambda n: "fl.optimizer" in n,
    "codec/draw": lambda n: "fl.codec/draw/" in n,
    "codec/dither": lambda n: "fl.codec/dither/" in n,
    "codec/encode": lambda n: "fl.codec/encode/" in n,
    "codec/psum": lambda n: "fl.codec/psum/" in n,
    "codec/decode": lambda n: "fl.codec/decode/" in n,
}
STEP = [s for s in SCOPES if s != "codec/psum"]
CASES = ([("step", s) for s in STEP]
         + [("pod_step", s) for s in SCOPES]
         + [("codec", s) for s in SCOPES if s.startswith("codec/")
            and s != "codec/psum"])


@pytest.mark.parametrize("program,scope", CASES)
def test_scope_reaches_op_name(program, scope):
    assert any(SCOPES[scope](n) for _op, n in instructions(program))


def test_no_matmul_or_kernel_outside_every_scope():
    outside = [(op, n) for op, n in instructions("step")
               if op in ("dot", "convolution", "custom-call")
               and not any(s in n for s in ("fl.forward", "fl.optimizer",
                                            "fl.codec"))]
    assert outside == []
