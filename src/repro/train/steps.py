"""Step builders: train_step / prefill_step / serve_step for every
(architecture x input shape) cell, with microbatching (gradient
accumulation), mixed precision, remat, and the paper's compressed
cross-client aggregation.

The same builders serve the real training driver (launch/train.py), the
smoke tests, and the multi-pod dry-run (inputs as ShapeDtypeStructs).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.dist import compress as compress_mod
from repro.dist import meshctx, sharding
from repro.models import nn, registry
from repro.models.config import ModelConfig
from repro.optim.optimizers import Optimizer, get_optimizer

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "adamw"
    lr: float = 3e-4
    grad_accum: int = 1
    compression: Optional[compress_mod.CompressionConfig] = None
    gather_once: bool = False  # ZeRO-1-style: materialize the bf16
    #   compute copy replicated-over-data ONCE per step instead of
    #   re-gathering per microbatch (Perf H2)


# ------------------------------------------------------------- inputs
def input_specs(cfg: ModelConfig, shape_name: str) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    sh = configs.SHAPES[shape_name]
    B, T = sh["global_batch"], sh["seq_len"]
    dt = jnp.dtype(cfg.compute_dtype)
    if sh["step"] == "decode":
        specs = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
        if cfg.kind == "whisper":
            pass  # cross-kv handled via decode state
        return specs
    specs = {"tokens": jax.ShapeDtypeStruct((B, T), jnp.int32)}
    if cfg.kind == "whisper":
        specs["frames"] = jax.ShapeDtypeStruct((B, cfg.encoder_len, cfg.d_model), dt)
    if cfg.kind == "llava":
        specs["tokens"] = jax.ShapeDtypeStruct((B, T - cfg.n_patches), jnp.int32)
        specs["patches"] = jax.ShapeDtypeStruct((B, cfg.n_patches, cfg.d_model), dt)
    return specs


def batch_shardings(cfg: ModelConfig, shape_name: str, mesh: Mesh):
    return {
        k: NamedSharding(mesh, sharding.batch_spec(mesh, len(v.shape), v.shape[0]))
        for k, v in input_specs(cfg, shape_name).items()
    }


# ------------------------------------------------------------- train
def make_train_state_specs(cfg: ModelConfig, tc: TrainConfig):
    """Abstract {params, opt_state, step} tree (dry-run, no allocation)."""
    pspecs = registry.param_specs(cfg)
    abs_params = nn.abstract_params(pspecs)
    opt = get_optimizer(tc.optimizer, tc.lr)
    abs_opt = jax.eval_shape(opt.init, abs_params)
    return {"params": abs_params, "opt_state": abs_opt,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def train_state_shardings(cfg: ModelConfig, tc: TrainConfig, mesh: Mesh):
    """Shardings for {params, opt_state, step}: optimizer-state leaves
    mirror the sharding of the param with the same shape (AdamW m/v),
    scalars are replicated.  Compressed multi-pod steps use model-only
    sharding (see sharding.NO_FSDP_RULES)."""
    pspecs = registry.param_specs(cfg)
    rules = sharding.PARAM_RULES
    if getattr(cfg, "moe_ep", False):
        rules = sharding.EP_PARAM_RULES
    if tc.compression is not None and "pod" in mesh.axis_names:
        rules = sharding.NO_FSDP_RULES
    pshard = sharding.param_shardings(pspecs, mesh, rules)
    abs_state = make_train_state_specs(cfg, tc)

    by_shape = {}
    for sds, sh in zip(jax.tree.leaves(abs_state["params"]), jax.tree.leaves(pshard)):
        by_shape.setdefault(sds.shape, sh)

    def opt_leaf(leaf):
        return by_shape.get(leaf.shape, NamedSharding(mesh, P()))

    opt_shard = jax.tree.map(opt_leaf, abs_state["opt_state"])
    return {"params": pshard, "opt_state": opt_shard,
            "step": NamedSharding(mesh, P())}


def init_train_state(cfg: ModelConfig, tc: TrainConfig, key):
    pspecs = registry.param_specs(cfg)
    params = nn.init_params(pspecs, key)
    opt = get_optimizer(tc.optimizer, tc.lr)
    return {"params": params, "opt_state": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def restore_train_state(directory: str, cfg: ModelConfig, tc: TrainConfig,
                        mesh: Mesh, step: Optional[int] = None):
    """Elastic restore of a train state onto ``mesh``: leaf placement is
    re-resolved through the `dist.sharding` rule tables for the *target*
    mesh — the rule tables, not the checkpoint, decide placement, so a
    checkpoint written on a ``(pod=4, data, model)`` mesh restores onto
    ``(pod=2, ...)`` or ``(pod=8, ...)`` unchanged.  Returns
    ``(state, step)``; raises if no committed checkpoint exists."""
    from repro.checkpoint import checkpoint

    if step is None:
        step = checkpoint.latest_step(directory)
        if step is None:
            raise checkpoint.CheckpointError(
                f"no committed checkpoint under {directory}")
    abs_state = make_train_state_specs(cfg, tc)
    shardings = train_state_shardings(cfg, tc, mesh)
    return checkpoint.restore(directory, step, abs_state, shardings), step


def _split_microbatches(batch: Dict, accum: int) -> Dict:
    return {k: v.reshape((accum, v.shape[0] // accum) + v.shape[1:])
            for k, v in batch.items()}


def build_train_step(cfg: ModelConfig, tc: TrainConfig, mesh: Mesh):
    """Returns step(state, batch, seed) -> (state, metrics).

    With a 'pod' mesh axis and compression enabled, per-pod (per-client)
    gradients are aggregated by the AINQ mechanism (integer psum across
    pods); otherwise gradients are standard global means (and the n=1
    point-to-point mechanism still applies exact noise if configured).
    """
    loss_fn = registry.loss_fn(cfg)
    opt = get_optimizer(tc.optimizer, tc.lr)
    has_pod = "pod" in mesh.axis_names
    n_clients = mesh.shape["pod"] if has_pod else 1
    comp = tc.compression

    def _compute_copy(p):
        # hoist the compute-dtype cast ABOVE the layer scan: ZeRO
        # all-gathers then move bf16 instead of f32; with gather_once the
        # compute copy is additionally replicated over the FSDP axis up
        # front (ONE gather per step, ZeRO-1 style — §Perf H2).
        p_c = nn.cast_tree(p, jnp.dtype(cfg.compute_dtype))
        if tc.gather_once:
            pspecs = registry.param_specs(cfg)
            resident = sharding.param_shardings(
                pspecs, mesh, sharding.SERVE_RESIDENT_RULES)
            p_c = jax.tree.map(jax.lax.with_sharding_constraint, p_c, resident)
        return p_c

    def grads_of(params, batch):
        # NOTE (§Perf H2, refuted): hoisting the gather/cast outside the
        # microbatch scan (differentiating one scan-of-losses) makes the
        # backward save residuals for ALL microbatches — 134 GB/chip
        # measured vs 16.5 GB for per-microbatch value_and_grad. ZeRO-1
        # style gather-once needs manual double-buffered scheduling that
        # GSPMD cannot express; kept per-microbatch here.
        def mb_loss(p, mb):
            # under value_and_grad the backward is traced as
            # transpose(jvp(fl.forward)), the remat recompute inside it
            with jax.named_scope("fl.forward"):
                return loss_fn(_compute_copy(p), mb)

        if tc.grad_accum <= 1:
            return jax.value_and_grad(mb_loss)(params, batch)
        mbs = _split_microbatches(batch, tc.grad_accum)

        def body(carry, mb):
            l, g = jax.value_and_grad(mb_loss)(params, mb)
            loss_acc, g_acc = carry
            return (loss_acc + l, jax.tree.map(jnp.add, g_acc, g)), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (l, g), _ = jax.lax.scan(body, (jnp.zeros(()), zeros), mbs)
        inv = 1.0 / tc.grad_accum
        return l * inv, jax.tree.map(lambda x: x * inv, g)

    def apply_update(state, grads, loss, cohort):
        with jax.named_scope("fl.optimizer"):
            updates, opt_state = opt.update(grads, state["opt_state"],
                                            state["params"])
            params = jax.tree.map(jnp.add, state["params"], updates)
        return (
            {"params": params, "opt_state": opt_state, "step": state["step"] + 1},
            {"loss": loss, "cohort": cohort},
        )

    if comp is not None and has_pod:
        # Per-client (per-pod) grads via vmap over a leading client axis
        # under plain GSPMD, then compressed cross-pod aggregation in a
        # small fully-manual shard_map over the gradient leaves only.
        # (Partially-manual shard_map around the whole backward — the
        # obvious design — hard-crashes XLA <= 0.4.x when the body
        # differentiates a scan: hlo_sharding_util IsManualSubgroup
        # check; see repro.dist README.)
        def step(state, batch, seed):
            def client_grads(mb):
                with meshctx.manual_axes({"pod"}):
                    # 'pod' is spoken for by the client axis: activation
                    # constraints must not re-shard per-client batches
                    # over it.
                    return grads_of(state["params"], mb)

            clients = {
                k: v.reshape((n_clients, v.shape[0] // n_clients) + v.shape[1:])
                for k, v in batch.items()
            }
            losses, grads = jax.vmap(client_grads)(clients)

            key = jax.random.fold_in(jax.random.PRNGKey(seed), state["step"])

            def aggregate(g, k):
                local = jax.tree.map(lambda t: t[0], g)  # this pod's client
                agg = compress_mod.compress_tree(
                    local, comp, k, axis="pod", n_clients=n_clients
                )
                # realized cohort: pods actually contributing to the psum
                # (drives the DP accounting in examples/dp_federated_training)
                realized = jax.lax.psum(jnp.ones((), jnp.int32), "pod")
                return agg, realized

            grads, realized = jax.shard_map(
                aggregate,
                mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P("pod"), grads), P()),
                out_specs=(jax.tree.map(lambda _: P(), grads), P()),
                check_vma=False,
            )(grads, key)
            return apply_update(state, grads, jnp.mean(losses), realized)

        return step

    def step(state, batch, seed):
        loss, grads = grads_of(state["params"], batch)
        if comp is not None:  # n=1 point-to-point exact-noise quantization
            key = jax.random.fold_in(jax.random.PRNGKey(seed), state["step"])
            grads = compress_mod.compress_tree(
                grads, comp, key, axis=None, n_clients=1
            )
        return apply_update(state, grads, loss, jnp.int32(n_clients))

    return step


# ------------------------------------------------------------- serving
def build_prefill_step(cfg: ModelConfig):
    fn = registry.prefill_fn(cfg)

    def prefill(params, batch):
        logits, caches = fn(params, batch)
        return logits, caches

    return prefill


def build_serve_step(cfg: ModelConfig):
    fn = registry.serve_fn(cfg)

    def serve(params, batch, cache):
        return fn(params, batch, cache)

    return serve
