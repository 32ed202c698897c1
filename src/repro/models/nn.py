"""Minimal functional module system: params are pytrees, sharding is
metadata.

Each model defines ``param_specs(cfg) -> nested dict of ParamSpec``; the
same spec tree yields (a) real initialized params, (b) abstract
ShapeDtypeStructs for the dry-run, and (c) a logical-axes tree that the
sharding rules (repro.dist.sharding) map onto the mesh.  Layer stacks
carry a leading 'layers' axis and are consumed with ``lax.scan`` so
compile time is O(1) in depth.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis name per dim (None = replicated)
    init: str = "normal"  # normal | zeros | ones | embed
    scale: float = 1.0
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _init_one(key, spec: ParamSpec):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init in ("normal", "embed"):
        # fan-in scaled normal; 'embed' scales by 1.0
        if spec.init == "embed" or len(spec.shape) < 2:
            std = spec.scale * 0.02
        else:
            fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[0]
            std = spec.scale / math.sqrt(max(fan_in, 1))
        return std * jax.random.normal(key, spec.shape, spec.dtype)
    raise ValueError(spec.init)


def _map_specs(fn: Callable[[Tuple[str, ...], ParamSpec], Any], specs: PyTree):
    def rec(path, node):
        if is_spec(node):
            return fn(path, node)
        if isinstance(node, dict):
            return {k: rec(path + (k,), v) for k, v in node.items()}
        raise TypeError(f"bad spec node at {path}: {type(node)}")

    return rec((), specs)


def init_params(specs: PyTree, key) -> PyTree:
    """Materialize parameters; deterministic per-path keys.  The path
    names are folded in by CRC32: ``hash(str)`` changes with every
    process, so the same seed would give other weights in each run."""

    def make(path, spec):
        k = key
        for p in path:
            k = jax.random.fold_in(k, zlib.crc32(str(p).encode()) & 0x7FFFFFFF)
        return _init_one(k, spec)

    return _map_specs(make, specs)


def abstract_params(specs: PyTree) -> PyTree:
    """ShapeDtypeStruct tree — the dry-run's no-allocation stand-in."""
    return _map_specs(lambda _, s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs)


def logical_axes(specs: PyTree) -> PyTree:
    return _map_specs(lambda _, s: s.axes, specs)


def cast_tree(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )


# ---------------------------------------------------------------- layers
def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * weight.astype(dt)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(dt) * weight.astype(dt) + bias.astype(dt)


def dense(x, w, b=None):
    y = jnp.einsum("...i,io->...o", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(dense(x, w_gate)) * dense(x, w_up)
    return dense(h, w_down)


def gelu_mlp(x, w_up, b_up, w_down, b_down):
    return dense(jax.nn.gelu(dense(x, w_up, b_up)), w_down, b_down)


# ---------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, max_t: int, theta: float = 10_000.0, dtype=jnp.float32):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_t, dtype=jnp.float32)
    ang = jnp.outer(t, inv)
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def rope_at(head_dim: int, positions, theta: float = 10_000.0, dtype=jnp.float32):
    """cos/sin evaluated at explicit (possibly traced, per-sequence)
    integer positions — decode never needs a table sized to the longest
    context.  positions: (...,) -> cos/sin (..., D/2).  Bitwise equal to
    indexing a ``rope_freqs`` table at the same positions."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope_direct(x, cos, sin):
    """x: (..., T, H, D); cos/sin already gathered per token (..., T, D/2)."""
    cos = cos[..., :, None, :].astype(x.dtype)
    sin = sin[..., :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(x, cos, sin, positions=None):
    """x: (..., T, H, D). cos/sin: (T_max, D/2). positions: (..., T) or None."""
    if positions is not None:
        cos = cos[positions]
        sin = sin[positions]
    else:
        cos = cos[: x.shape[-3]]
        sin = sin[: x.shape[-3]]
    return apply_rope_direct(x, cos, sin)


def cross_entropy_loss(logits, labels, mask=None):
    """Mean token NLL. logits (..., V) f32-accumulated; labels int (...,)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def shard_activation(x, logical_axes):
    """with_sharding_constraint via the active mesh + ACT_RULES.
    ``logical_axes``: tuple of logical names (or None) per dim."""
    from jax.sharding import NamedSharding

    from repro.dist import meshctx, sharding as shd

    mesh = meshctx.get_mesh()
    if math.prod(mesh.devices.shape) == 1:
        return x
    manual = meshctx.get_manual_axes()
    rules = tuple(
        (name, tuple(a for a in ((ax,) if isinstance(ax, str) else ax)
                     if a not in manual) or None if ax is not None else None)
        for name, ax in shd.ACT_RULES
    )
    spec = shd.spec_for_axes(logical_axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
