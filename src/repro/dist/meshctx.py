"""Process-global mesh context.

One mesh per process, three axes:

  pod   — FL clients / cross-site data parallelism; the compressed
          aggregation (repro.dist.compress) psums over this axis
  data  — within-pod data parallelism + ZeRO/FSDP param sharding
  model — tensor parallelism

``make_mesh`` is the one mesh constructor: every axis is
``AxisType.Auto``, so GSPMD propagates shardings and the activation
constraints of ``nn.shard_activation`` may name any axis (jax's own
``make_mesh`` defaults to ``Explicit`` axes, which reject them).
``default_mesh()`` shapes a (pod, data, model) mesh over whatever
devices exist.

``manual_axes({...})`` records which mesh axes are currently manual
(inside a ``shard_map``); ``nn.shard_activation`` and
``meshctx.batch_axes`` subtract those axes from the specs they emit so
GSPMD constraints issued inside the manual region never mention an
already-manual axis.
"""
from __future__ import annotations

import contextlib
from typing import FrozenSet, Iterable, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh

AXES = ("pod", "data", "model")

_mesh: Optional[Mesh] = None
_manual: FrozenSet[str] = frozenset()


def make_mesh(shape: Sequence[int], axes: Sequence[str] = AXES, *,
              devices=None) -> Mesh:
    """A mesh of ``shape`` over ``axes`` with every axis Auto.
    ``devices`` defaults to ``jax.devices()``; pass a described
    topology's devices to compile for a chip that is not attached."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def default_mesh() -> Mesh:
    """A (pod, data, model) mesh over all available devices.

    Axis sizes are picked so every axis is as close to uniform as the
    device count allows: 8 devices -> (2, 2, 2), 4 -> (2, 1, 2),
    2 -> (2, 1, 1), 1 -> (1, 1, 1).
    """
    n = len(jax.devices())
    pod = 2 if n % 2 == 0 and n > 1 else 1
    rem = n // pod
    model = 2 if rem % 2 == 0 and rem > 1 else 1
    data = rem // model
    return make_mesh((pod, data, model))


def set_mesh(mesh: Mesh) -> None:
    global _mesh
    _mesh = mesh


def get_mesh() -> Mesh:
    global _mesh
    if _mesh is None:
        _mesh = default_mesh()
    return _mesh


# ------------------------------------------------------------ manual axes
@contextlib.contextmanager
def manual_axes(axes: Iterable[str]):
    """Record ``axes`` as manual for the duration of the context (used
    around code traced inside a ``shard_map`` over those axes)."""
    global _manual
    prev = _manual
    _manual = prev | frozenset(axes)
    try:
        yield
    finally:
        _manual = prev


def get_manual_axes() -> FrozenSet[str]:
    return _manual


# ------------------------------------------------------- axis utilities
def _usable(mesh: Mesh, name: str) -> bool:
    return (
        name in mesh.axis_names
        and mesh.shape[name] > 1
        and name not in _manual
    )


def batch_axes(mesh: Mesh, dim: Optional[int] = None) -> Tuple[str, ...]:
    """Mesh axes a batch dimension shards over: the (pod, data) prefix
    whose size product divides ``dim`` (all of it when ``dim`` is None).
    Size-1 and currently-manual axes are dropped."""
    axes = [a for a in ("pod", "data") if _usable(mesh, a)]
    if dim is None:
        return tuple(axes)
    picked, prod = [], 1
    for a in axes:
        if dim % (prod * mesh.shape[a]) == 0:
            picked.append(a)
            prod *= mesh.shape[a]
        else:
            break
    return tuple(picked)


def model_axis(mesh: Mesh) -> Optional[str]:
    return "model" if _usable(mesh, "model") else None
