"""Production mesh builders.

Functions (not module-level constants) so importing never touches jax
device state.  Production target: TPU v5e, 256 chips/pod (16 x 16),
2 pods for the multi-pod dry-run.  Axes:

  pod   — FL clients / cross-site data parallelism (compressed
          aggregation runs over this axis; see repro.dist.compress)
  data  — within-pod data parallelism + ZeRO/FSDP param sharding
  model — tensor parallelism

Both build through ``repro.dist.meshctx.make_mesh`` (all axes Auto).
"""
from __future__ import annotations

from repro.dist.meshctx import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (host) devices exist — tests/examples."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
