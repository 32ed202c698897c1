"""Serving launcher: thin driver over the continuous-batching engine.

Requests stream through a queue into a fixed pool of KV-cache slots
(repro.serve.ServeEngine); slots are evicted on EOS / per-request
max-gen / cache capacity and immediately refilled, so the resident
decode step stays busy at high occupancy.  ``--naive`` runs the
pre-engine lockstep loop (repro.serve.oracle) instead — the engine's
correctness oracle and the tokens/sec baseline.

CPU-container usage (reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --smoke \
      --requests 8 --slots 4 --prompt-len 16 --gen 8

Without --smoke the same entry point serves the full config.
"""
from __future__ import annotations

import argparse
import time
from collections import deque

import jax
import numpy as np

from repro import compile_cache, configs
from repro.data import synthetic
from repro.dist import meshctx
from repro.models import nn, registry
from repro.launch.mesh import make_host_mesh
from repro.serve import ServeEngine, naive_generate


def drive(engine: ServeEngine, params, requests, *, log=lambda *_: None):
    """Pump ``requests`` (iterable of (rid, tokens, max_gen)) through the
    slot pool.  Returns (outputs {rid: [token ids]}, stats dict with
    step/occupancy accounting)."""
    state = engine.init_state()
    free = list(range(engine.ecfg.max_slots))
    pending = deque(requests)
    outputs: dict = {}
    slot_rid: dict = {}
    steps = 0
    occ_sum = 0.0
    tokens_out = 0
    t0 = time.perf_counter()
    while pending or slot_rid:
        while free and pending:
            rid, toks, max_gen = pending.popleft()
            _, prefix = engine.prefill(params, toks)
            slot = free.pop()
            state = engine.insert(state, prefix, slot, max_gen=max_gen)
            outputs[rid] = [int(prefix.next_token)]
            tokens_out += 1
            if max_gen <= 1:  # satisfied by the prefill token alone
                free.append(slot)
                log(f"[serve] rid={rid} done at insert (max_gen=1)")
            else:
                slot_rid[slot] = rid
        if not slot_rid:
            continue
        occ_sum += len(slot_rid) / engine.ecfg.max_slots
        state, toks, done = engine.generate_step(params, state)
        steps += 1
        toks_h, done_h = np.asarray(toks), np.asarray(done)
        for slot, rid in list(slot_rid.items()):
            outputs[rid].append(int(toks_h[slot]))
            tokens_out += 1
            if done_h[slot]:
                del slot_rid[slot]
                free.append(slot)
                log(f"[serve] rid={rid} done ({len(outputs[rid])} tokens), "
                    f"slot {slot} freed")
    dt = time.perf_counter() - t0
    return outputs, {
        "steps": steps,
        "tokens_out": tokens_out,
        "wall_s": dt,
        "mean_occupancy": occ_sum / steps if steps else 0.0,
        "tokens_per_s": tokens_out / dt if dt > 0 else 0.0,
    }


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8,
                    help="tokens per request (prefill token included)")
    ap.add_argument("--eos", type=int, default=None,
                    help="token id treated as EOS (frees the slot early)")
    ap.add_argument("--naive", action="store_true",
                    help="run the lockstep oracle loop instead")
    ap.add_argument("--batch", type=int, default=2,
                    help="(--naive only) lockstep batch size")
    return ap


def _setup(args):
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.smoke:
        cfg = cfg.scaled(compute_dtype="float32")
    mesh = make_host_mesh(data=len(jax.devices()), model=1)
    meshctx.set_mesh(mesh)
    params = nn.init_params(registry.param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def run_engine(args, log=print):
    """The engine path of ``main``: ``args.requests`` random prompts of
    ``args.prompt_len`` tokens through ``args.slots`` slots.  Returns
    (cfg, outputs {rid: [token ids]}, stats)."""
    cfg, params = _setup(args)
    P = args.prompt_len
    engine = ServeEngine(cfg, max_slots=args.slots, max_prefill_len=P,
                         max_gen_len=args.gen, eos_id=args.eos)
    rng = np.random.default_rng(1)
    requests = [
        (r, rng.integers(0, cfg.vocab, size=(P,), dtype=np.int32), args.gen)
        for r in range(args.requests)
    ]
    outputs, stats = drive(engine, params, requests, log=log)
    log(f"[serve] {args.requests} requests x {args.gen} tokens on "
        f"{args.slots} slots: {stats['tokens_out']} tokens, "
        f"{stats['steps']} steps in {stats['wall_s']:.2f}s "
        f"({stats['tokens_per_s']:.1f} tok/s, "
        f"mean occupancy {stats['mean_occupancy']:.0%})")
    log(f"[serve] sample token ids: {outputs[0]}")
    return cfg, outputs, stats


def main():
    args = build_parser().parse_args()
    compile_cache.use_persistent_cache()
    if not args.naive:
        run_engine(args)
        return
    cfg, params = _setup(args)
    B, P = args.batch, args.prompt_len
    prompts = synthetic.with_frontend_stubs(
        {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (B, P), 0, cfg.vocab)}, cfg)
    t0 = time.perf_counter()
    toks = naive_generate(cfg, params, prompts, args.gen)
    dt = time.perf_counter() - t0
    print(f"[serve] naive {B}x{args.gen} tokens in {dt:.2f}s "
          f"({B * args.gen / dt:.1f} tok/s)")
    print("[serve] sample token ids:", toks[0].tolist())


if __name__ == "__main__":
    main()
