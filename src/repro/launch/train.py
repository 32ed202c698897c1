"""Production training launcher.

Selects an assigned architecture (--arch), a mesh, an AINQ compression
mechanism for the cross-client aggregation, and runs the fault-tolerant
training loop: deterministic restartable data stream, periodic
checkpoints, automatic resume from the latest committed checkpoint
(crash/preemption recovery), elastic restore onto a different mesh.

CPU-container usage (reduced config smoke):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --smoke --steps 20 --mechanism aggregate_gaussian

Async actor/learner mode (repro.runtime): N client processes/threads
exchange integer messages with a staleness-aware learner —
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --smoke --runtime async --transport process --clients 3 --rounds 2 \
      --mechanism aggregate_gaussian --sigma 1e-3 --no-per-coord

On a TPU pod the same entry point runs the full config with
--mesh data,model axes sized by the slice topology.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, List, NamedTuple

import jax
import jax.numpy as jnp

from repro import compile_cache, configs
from repro.checkpoint import checkpoint
from repro.data import synthetic
from repro.dist import meshctx
from repro.dist.compress import CompressionConfig
from repro.launch.mesh import make_host_mesh
from repro.train import steps


def run_async(args) -> None:
    """Async actor/learner FL: integer-message rounds over a real
    transport, staleness-aware aggregation (see repro/runtime/README)."""
    import json

    from repro.fl.federated import FLConfig
    from repro.runtime import (
        AsyncFederatedRuntime,
        ModelGradWorkload,
        RuntimeConfig,
    )

    from repro.runtime import chaos as chaos_mod

    if args.mechanism == "none":
        raise SystemExit(
            "--runtime async needs a mechanism with an integer wire "
            "format (e.g. aggregate_gaussian); 'none' has none"
        )
    seq = args.seq or (32 if args.smoke else 4096)
    batch = args.batch or (2 if args.smoke else 256)
    plan = None
    if args.chaos:
        plan = chaos_mod.parse_plan(args.chaos, seed=0,
                                    delay_s=args.chaos_delay,
                                    rejoin_after_s=args.chaos_rejoin)
        print(f"[train] chaos plan: {plan}")
    fl = FLConfig(
        n_clients=args.clients, mechanism=args.mechanism, sigma=args.sigma,
        clip=args.clip, cohort_fraction=args.cohort_fraction, lr=args.lr,
        mech_kwargs=(("per_coord", args.per_coord),
                     ("packed", args.fused),
                     ("msg_bits", args.msg_bits)),
    )
    rc = RuntimeConfig(
        fl=fl, staleness_bound=args.staleness_bound,
        staleness_weighting=args.staleness_weighting, quorum=args.quorum,
        round_timeout_s=args.round_timeout, transport=args.transport,
        straggler_fraction=args.straggler_fraction,
        straggler_delay_s=args.straggler_delay,
        heartbeat_timeout_s=args.heartbeat_timeout,
        chaos=plan,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        resume=args.resume,
    )
    wl = ModelGradWorkload(arch=args.arch, smoke=args.smoke, seq=seq,
                           batch=batch, data=args.data)
    print(f"[train] async runtime: {args.clients} clients over "
          f"{args.transport} transport, staleness bound "
          f"{args.staleness_bound}, mechanism {args.mechanism}")
    t0 = time.time()
    params0 = wl.init_params()
    rt = AsyncFederatedRuntime(rc, wl)
    params, summary, _ = rt.run(params0, args.rounds)
    drift = float(jnp.linalg.norm(jnp.asarray(params) - jnp.asarray(params0)))
    print(f"[train] {summary['rounds']} rounds in {time.time() - t0:.1f}s "
          f"({summary['rounds_per_sec']:.2f} rounds/s), occupancy "
          f"{summary['mean_cohort_occupancy']:.2f}, "
          f"{summary['bits_per_round']:.0f} bits/round, |dparams| {drift:.3g}")
    print(f"[train] membership: {summary.get('active_members_final')} final "
          f"members, {summary.get('evictions', 0)} evictions, "
          f"{summary.get('joins', 0)} joins, "
          f"{summary.get('degraded_rounds', 0)} degraded rounds, "
          f"{summary.get('learner_restarts', 0)} learner restarts")
    if summary.get("empty_rounds"):
        raise SystemExit(f"{summary['empty_rounds']} empty rounds — no "
                         f"client updates landed; transport broken?")
    if plan is not None and plan.any_faults:
        # chaos acceptance: the failure must be visible in the realized
        # cohort accounting — a run that claims full occupancy while a
        # client was crashed would mean the metrics lie
        if not (summary.get("degraded_rounds", 0)
                or summary.get("evictions", 0)
                or summary.get("learner_restarts", 0)):
            raise SystemExit("chaos plan injected faults but the realized-"
                             "cohort metrics show no degradation — fault "
                             "injection broken?")
    if args.bench_out:
        with open(args.bench_out, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
        print(f"[train] wrote {args.bench_out}")
    print("[train] done")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mechanism", default="none")
    ap.add_argument("--sigma", type=float, default=1e-4)
    ap.add_argument("--clip", type=float, default=1.0)
    ap.add_argument("--per-coord", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="per-coordinate shared randomness (paper-faithful "
                         "i.i.d. noise); --no-per-coord draws per tensor")
    ap.add_argument("--fused", action="store_true",
                    help="fused encode/decode kernels with true-bit-width "
                         "packed collectives (homomorphic mechanisms only); "
                         "async runtime: packed client uplink")
    ap.add_argument("--msg-bits", type=int, default=None,
                    help="packed field width (2..24); default: widest for "
                         "the msg dtype")
    ap.add_argument("--checkpoint-dir", "--ckpt", dest="checkpoint_dir",
                    default=None,
                    help="async sharded checkpoint directory (commit "
                         "barrier + keep-last-k retention)")
    ap.add_argument("--checkpoint-every", "--ckpt-every",
                    dest="checkpoint_every", type=int, default=50,
                    help="steps (sync) / rounds (async) between checkpoints")
    ap.add_argument("--keep-last-k", type=int, default=3,
                    help="checkpoints retained by GC (newest never deleted)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest committed checkpoint in "
                         "--checkpoint-dir (elastic: the target mesh may "
                         "differ from the mesh the checkpoint was saved on)")
    ap.add_argument("--data", default="lm", choices=["lm", "uniform"])
    # --- async actor/learner runtime (repro.runtime) ---
    ap.add_argument("--runtime", default="sync", choices=["sync", "async"])
    ap.add_argument("--transport", default="thread",
                    choices=["thread", "process"],
                    help="process: one OS process per client, CPU only "
                         "(a chip belongs to one process)")
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--staleness-bound", type=int, default=0)
    ap.add_argument("--staleness-weighting", default="uniform",
                    choices=["uniform", "inverse"])
    ap.add_argument("--quorum", type=float, default=1.0)
    ap.add_argument("--round-timeout", type=float, default=120.0)
    ap.add_argument("--cohort-fraction", type=float, default=1.0)
    ap.add_argument("--straggler-fraction", type=float, default=0.0,
                    help="wall-clock straggler probability per (client, "
                         "round) in async mode")
    ap.add_argument("--straggler-delay", type=float, default=0.5)
    ap.add_argument("--heartbeat-timeout", type=float, default=10.0,
                    help="async: members silent this long are evicted "
                         "from future cohorts (clients beacon at 1/4)")
    ap.add_argument("--chaos", default=None,
                    help="async fault plan, e.g. 'client_crash@1:2,"
                         "learner_crash@3' or 'crash_rate=0.2' "
                         "(see repro.runtime.chaos.parse_plan)")
    ap.add_argument("--chaos-delay", type=float, default=0.25,
                    help="hold time for delay/slow_uplink faults")
    ap.add_argument("--chaos-rejoin", type=float, default=None,
                    help="crashed clients rejoin after this many seconds "
                         "(default: crashes are permanent)")
    ap.add_argument("--bench-out", default=None,
                    help="write the async run summary as JSON here")
    return ap


class SyncRun(NamedTuple):
    """What ``run_sync`` leaves behind: the final state, every step's
    loss, the jitted step and the last batch it was called with (so a
    caller can ``step_fn.lower(state, batch, seed)`` the program that
    ran)."""

    state: Dict[str, Any]
    losses: List[float]
    step_fn: Any
    batch: Dict[str, Any]


def run_sync(args, log=print) -> SyncRun:
    """The synchronous training loop behind ``main``."""
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.smoke:
        cfg = cfg.scaled(compute_dtype="float32")
    seq = args.seq or (32 if args.smoke else 4096)
    batch = args.batch or (4 if args.smoke else 256)

    n_dev = len(jax.devices())
    mesh = make_host_mesh(data=n_dev, model=1)
    meshctx.set_mesh(mesh)

    comp = None
    if args.mechanism != "none":
        comp = CompressionConfig(mechanism=args.mechanism, sigma=args.sigma,
                                 clip=args.clip, per_coord=args.per_coord,
                                 fused=args.fused, msg_bits=args.msg_bits)
    tc = steps.TrainConfig(optimizer="adamw", lr=args.lr,
                           grad_accum=args.grad_accum, compression=comp)
    state = steps.init_train_state(cfg, tc, jax.random.PRNGKey(0))
    if args.checkpoint_dir and (args.resume
                                or checkpoint.latest_step(args.checkpoint_dir)
                                is not None):
        last = checkpoint.latest_step(args.checkpoint_dir)
        if last is not None:
            # elastic restore: leaf placement re-resolved through the
            # sharding rule tables for THIS mesh (the checkpoint may have
            # been written on a different pod count)
            state, last = steps.restore_train_state(
                args.checkpoint_dir, cfg, tc, mesh)
            log(f"[train] resumed step {last} onto mesh "
                f"{dict(mesh.shape)}")

    ckpt = None
    if args.checkpoint_dir:
        ckpt = checkpoint.AsyncCheckpointer(
            args.checkpoint_dir, keep_last_k=args.keep_last_k,
            mesh_axes=dict(mesh.shape))

    # the state is donated: without it the old and new state (5.6 GB
    # each for full-width qwen1.5-0.5b) are live together and the step
    # no longer fits one v5e chip
    step_fn = jax.jit(steps.build_train_step(cfg, tc, mesh),
                      donate_argnums=0)
    dc = synthetic.DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                              kind=args.data)
    batch_fn = synthetic.batch_fn(dc)

    first = int(state["step"])
    losses: List[float] = []
    data = None
    t0 = time.time()
    for i in range(first, first + args.steps):
        data = synthetic.with_frontend_stubs(batch_fn(dc, i), cfg)
        state, m = step_fn(state, data, jnp.int32(i))
        losses.append(float(m["loss"]))
        if i % 10 == 0 or i == first + args.steps - 1:
            dt = time.time() - t0
            log(f"[train] step {i:6d} loss {losses[-1]:.4f} "
                f"({(i - first + 1) * batch * seq / max(dt, 1e-9):,.0f} tok/s)")
        if ckpt is not None and (i + 1) % args.checkpoint_every == 0:
            ckpt.save(i + 1, state)
            log(f"[train] checkpoint {i + 1} queued (async)")
    if ckpt is not None:
        ckpt.close()
    log("[train] done")
    return SyncRun(state, losses, step_fn, data)


def main():
    args = build_parser().parse_args()
    compile_cache.use_persistent_cache()
    if args.runtime == "async":
        return run_async(args)
    run_sync(args)


if __name__ == "__main__":
    main()
