"""Bring-up check on the chip: drive the system's entry points once at
full width and check what comes out.

    python chip_smoke.py               # one chip: train, codec, serve
    python chip_smoke.py --four-chips  # four chips: pod-axis aggregation
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny, any device

One chip (full-width qwen1.5-0.5b, random weights from a fixed seed):

  train  ``launch/train.py``'s sync loop with the fused per-coordinate
         ``aggregate_gaussian`` codec, two steps at 4 x 2048: every loss
         finite, and the step's compiled program holds the Pallas codec
         (``tpu_custom_call``).
  codec  fused per-coordinate ``aggregate_gaussian`` (n=1) on 2^22
         coordinates: decoded minus clipped input passes the KS test
         against N(0, sigma^2), the mechanism's exact law.
  serve  ``launch/serve.py``'s engine path: 8 requests on 4 slots,
         prompt 128, gen 16; every request returns 16 ids in [0, vocab).

Four chips (``--four-chips``; only these two phases):

  pod_step  ``steps.build_train_step`` on a (pod=4, data=1, model=1)
            mesh, one client per chip, per-tensor shared randomness:
            losses finite, realized cohort 4.
  pod_agg   ``compress_tree`` in a ``shard_map`` over ``pod`` on
            distinct per-pod inputs: its output minus the ``none_``
            (pmean) output passes the KS test against N(0, sigma^2).

Each phase prints one ``phase <name> {...}`` line with its compile and
wall seconds and the device.  The last line is the JSON result, printed
only when every phase passed on a TPU.  Off the chip, in a directory
without the rest of the repository, or with ``--rehearse`` the script
exits non-zero and prints no result.  Everything runs in this one
process: a chip belongs to the process that touched it first.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen1.5-0.5b"
CODEC_SIGMA = 0.05
CODEC_CLIP = 1.0
TRAIN_STEPS = 2
log = functools.partial(print, file=sys.stderr, flush=True)


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling, and persistent
    cache hits, read from jax's own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def ks_exact_gaussian(err, sigma):
    """KS statistic and threshold of ``err`` against N(0, sigma^2)."""
    from helpers import ks_statistic, ks_threshold, norm_cdf

    ks = ks_statistic(err, lambda s: norm_cdf(s, sigma))
    return ks, float(ks_threshold(len(err)))


def check(cond, what):
    if not cond:
        raise AssertionError(what)


# ------------------------------------------------------------ one chip
def phase_train(ctx):
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import train

    # two steps: on a v5e each spends about 9 s drawing the
    # per-coordinate DECOMPOSE (A, B) for the model's 464M coordinates
    argv = ["--arch", ARCH, "--mechanism", "aggregate_gaussian", "--fused",
            "--steps", str(TRAIN_STEPS)]
    argv += (["--smoke", "--batch", "4", "--seq", "32"] if ctx.rehearse
             else ["--batch", "4", "--seq", "2048"])
    run = train.run_sync(train.build_parser().parse_args(argv), log=log)
    check(len(run.losses) == TRAIN_STEPS and np.isfinite(run.losses).all(),
          f"losses {run.losses}")
    hlo = run.step_fn.lower(run.state, run.batch, jnp.int32(0)).compile(
    ).as_text()
    kernel = "tpu_custom_call" in hlo
    if ctx.on_tpu:
        check(kernel, "no tpu_custom_call in the train step: the fused "
                      "codec did not run as the Pallas kernel")
    tokens = run.batch["tokens"].shape
    return {"batch": tokens[0], "seq": tokens[1], "losses": run.losses,
            "tpu_custom_call": kernel}


def phase_codec(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.dist.compress import CompressionConfig, compress_tree

    d = 1 << (16 if ctx.rehearse else 22)
    comp = CompressionConfig(mechanism="aggregate_gaussian",
                             sigma=CODEC_SIGMA, clip=CODEC_CLIP,
                             per_coord=True, fused=True)
    x = jax.random.uniform(jax.random.PRNGKey(1), (d,), minval=-1.5,
                           maxval=1.5)
    fn = jax.jit(lambda v, k: compress_tree({"g": v}, comp, k)["g"])
    compiled = fn.lower(x, jax.random.PRNGKey(2)).compile()
    kernel = "tpu_custom_call" in compiled.as_text()
    if ctx.on_tpu:
        check(kernel, "no tpu_custom_call in the codec program")
    y = compiled(x, jax.random.PRNGKey(2))
    err = np.asarray(y - jnp.clip(x, -CODEC_CLIP, CODEC_CLIP), np.float64)
    ks, thr = ks_exact_gaussian(err, CODEC_SIGMA)
    check(ks < thr, f"KS {ks} >= {thr}: error is not N(0, sigma^2)")
    return {"coords": d, "ks": ks, "ks_threshold": thr,
            "err_std_over_sigma": float(err.std() / CODEC_SIGMA),
            "tpu_custom_call": kernel}


def phase_serve(ctx):
    from repro.launch import serve

    requests, gen = 8, (4 if ctx.rehearse else 16)
    argv = ["--arch", ARCH, "--requests", str(requests), "--slots", "4",
            "--gen", str(gen)]
    argv += (["--smoke", "--prompt-len", "8"] if ctx.rehearse
             else ["--prompt-len", "128"])
    cfg, outputs, stats = serve.run_engine(
        serve.build_parser().parse_args(argv), log=log)
    check(sorted(outputs) == list(range(requests)),
          f"requests answered: {sorted(outputs)}")
    for rid, toks in outputs.items():
        check(len(toks) == gen, f"request {rid}: {len(toks)} tokens")
        check(all(0 <= t < cfg.vocab for t in toks),
              f"request {rid}: ids outside [0, {cfg.vocab})")
    return {"requests": requests, "gen": gen,
            "tokens_out": stats["tokens_out"], "decode_steps": stats["steps"],
            "sample": outputs[0]}


# ---------------------------------------------------------- four chips
def _pod_mesh():
    import jax

    from repro.dist import meshctx

    n = len(jax.devices())
    check(n >= 4, f"--four-chips needs 4 devices, found {n}")
    return meshctx.make_mesh((4, 1, 1), devices=jax.devices()[:4])


def phase_pod_step(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.data import synthetic
    from repro.dist import meshctx
    from repro.dist.compress import CompressionConfig
    from repro.train import steps

    mesh = _pod_mesh()
    meshctx.set_mesh(mesh)
    if ctx.rehearse:
        cfg = configs.get_smoke_config(ARCH).scaled(compute_dtype="float32")
        batch, seq = 8, 32
    else:
        cfg = configs.get_config(ARCH)
        batch, seq = 16, 2048  # 4 x 2048 per client
    # per-tensor (A, B): on a v5e the per-coordinate draw at n=4 takes
    # 33 s per 2^22 coordinates, about an hour per step for the model's
    # 464M; pod_agg checks the per-coordinate law
    comp = CompressionConfig(mechanism="aggregate_gaussian", sigma=1e-4,
                             clip=1.0, per_coord=False, fused=True)
    tc = steps.TrainConfig(optimizer="adamw", lr=3e-4, compression=comp)
    shardings = steps.train_state_shardings(cfg, tc, mesh)
    state = jax.jit(functools.partial(steps.init_train_state, cfg, tc),
                    out_shardings=shardings)(jax.random.PRNGKey(0))
    step = jax.jit(steps.build_train_step(cfg, tc, mesh), donate_argnums=0)
    dc = synthetic.DataConfig(vocab=cfg.vocab, seq_len=seq,
                              global_batch=batch)
    losses, cohorts = [], []
    for i in range(3):
        state, m = step(state, synthetic.lm_batch(dc, i), jnp.int32(i))
        losses.append(float(m["loss"]))
        cohorts.append(int(m["cohort"]))
        log(f"[pod_step] step {i} loss {losses[-1]:.4f} cohort {cohorts[-1]}")
    check(np.isfinite(losses).all(), f"losses {losses}")
    check(cohorts == [4, 4, 4], f"realized cohorts {cohorts}")
    return {"batch": batch, "seq": seq, "n_layers": cfg.n_layers,
            "losses": losses, "realized_cohort": cohorts}


def phase_pod_agg(ctx):
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.dist.compress import CompressionConfig, compress_tree

    mesh = _pod_mesh()
    n, d = 4, 1 << (14 if ctx.rehearse else 22)
    xs = jax.random.uniform(jax.random.PRNGKey(3), (n, d), minval=-1.5,
                            maxval=1.5)

    def aggregate(mechanism):
        comp = CompressionConfig(mechanism=mechanism, sigma=CODEC_SIGMA,
                                 clip=CODEC_CLIP, per_coord=True,
                                 fused=mechanism != "none_")

        def per_pod(g, key):
            return compress_tree({"g": g[0]}, comp, key, axis="pod",
                                 n_clients=n)["g"]

        return jax.jit(jax.shard_map(
            per_pod, mesh=mesh, in_specs=(P("pod"), P()), out_specs=P(),
            check_vma=False))(xs, jax.random.PRNGKey(4))

    err = np.asarray(aggregate("aggregate_gaussian") - aggregate("none_"),
                     np.float64)
    ks, thr = ks_exact_gaussian(err, CODEC_SIGMA)
    check(ks < thr, f"KS {ks} >= {thr}: aggregate error is not N(0, s^2)")
    return {"clients": n, "coords": d, "ks": ks, "ks_threshold": thr,
            "err_std_over_sigma": float(err.std() / CODEC_SIGMA)}


# ----------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pod-axis phases, on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke-sized configs on any device; never prints "
                         "a result")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]
    try:
        from repro import compile_cache
    except ImportError as e:
        log(f"chip_smoke: the repository is not next to this script: {e}")
        return 2
    import jax

    compile_cache.use_persistent_cache()
    devices = jax.devices()
    dev = devices[0]
    args.on_tpu = dev.platform == "tpu"
    if not (args.on_tpu or args.rehearse):
        log(f"chip_smoke: no TPU (jax found {dev.platform!r})")
        return 1
    clock = CompileClock(jax.monitoring)
    phases = ([phase_pod_step, phase_pod_agg] if args.four_chips
              else [phase_train, phase_codec, phase_serve])
    failed = []
    for phase in phases:
        name = phase.__name__.removeprefix("phase_")
        c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
        try:
            facts = phase(args)
        except Exception:  # noqa: BLE001 — report it, run the other phases
            traceback.print_exc()
            facts, ok = {}, False
            failed.append(name)
        else:
            ok = True
        line = {"ok": ok, "compile_s": clock.seconds - c0,
                "wall_s": time.perf_counter() - t0,
                "cache_hits": clock.cache_hits - h0,
                "platform": dev.platform, "kind": dev.device_kind, **facts}
        print(f"phase {name} {json.dumps(line, default=str)}", flush=True)
    if failed:
        log(f"chip_smoke: failed phases: {failed}")
        return 1
    if not args.on_tpu or args.rehearse:
        log(f"chip_smoke: rehearsal passed on {dev.platform}; no result "
            f"is printed off the chip")
        return 3
    count = 4 if args.four_chips else len(devices)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
