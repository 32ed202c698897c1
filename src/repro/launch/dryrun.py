import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes and record memory / cost / collective
statistics.

No arrays are allocated: all inputs are ShapeDtypeStructs; the compiled
executable is inspected, never executed.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
      --shape train_4k [--multi-pod] [--out artifacts/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs  # noqa: E402
from repro.dist import meshctx, sharding  # noqa: E402
from repro.dist.compress import CompressionConfig  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.train import steps  # noqa: E402

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    m = _SHAPE_RE.match(shape_str)
    if not m:
        return 0
    dt, dims = m.group(1), m.group(2)
    if dt == "token" or dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str):
    """Sum result-shape bytes of every collective op in the optimized HLO,
    bucketed by op kind. (Per-device payload proxy; see EXPERIMENTS.md.)"""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"\S+ = (\(?.*?\)?) ([\w-]+)\(", s)
        if not m:
            continue
        op = m.group(2)
        kind = None
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-"):
                kind = c
                break
        if kind is None:
            continue
        total = 0
        for sm in _SHAPE_RE.finditer(m.group(1)):  # handles tuples + layouts
            dt, dims = sm.group(1), sm.group(2)
            if dt in _DTYPE_BYTES:
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                total += n * _DTYPE_BYTES[dt]
        out[kind] += total
        counts[kind] += 1
    return out, counts


def build_cell(arch: str, shape_name: str, mesh, compress: str = "none",
               opts: dict | None = None):
    """Returns (fn, args, in_shardings) ready to lower.

    ``opts`` (perf-iteration knobs, EXPERIMENTS.md par. Perf):
      remat: override remat policy ("full"|"dots"|"none")
      accum: override grad accumulation
      msg_dtype: compression psum payload ("int32"|"int16"|"int8")
      serve_resident: serving weights resident (no ZeRO gather)
      serve_bf16: serving weights stored bf16
    """
    opts = opts or {}
    cfg = configs.get_config(arch)
    if opts.get("remat"):
        cfg = cfg.scaled(remat=opts["remat"])
    if opts.get("moe_ep"):
        cfg = cfg.scaled(moe_ep=True)
    meshctx.set_mesh(mesh)
    sh = configs.SHAPES[shape_name]
    comp = None
    if compress != "none":
        comp = CompressionConfig(mechanism=compress, sigma=1e-4, clip=1.0,
                                 msg_dtype=opts.get("msg_dtype", "int32"))
    tc = steps.TrainConfig(
        optimizer="adamw", lr=1e-4,
        grad_accum=opts.get("accum") or _grad_accum(arch, shape_name),
        compression=comp, gather_once=bool(opts.get("gather_once")),
    )

    if sh["step"] == "train":
        state = steps.make_train_state_specs(cfg, tc)
        state_sh = steps.train_state_shardings(cfg, tc, mesh)
        batch = steps.input_specs(cfg, shape_name)
        batch_sh = steps.batch_shardings(cfg, shape_name, mesh)
        step = steps.build_train_step(cfg, tc, mesh)
        seed = jax.ShapeDtypeStruct((), jnp.int32)
        return (
            step,
            (state, batch, seed),
            (state_sh, batch_sh, NamedSharding(mesh, P())),
        )

    # inference: params only (no optimizer state)
    from repro.models import nn

    pspecs = registry.param_specs(cfg)
    params = nn.abstract_params(pspecs)
    if opts.get("serve_bf16"):
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), params)
    rules = (sharding.SERVE_RESIDENT_RULES if opts.get("serve_resident")
             else (sharding.EP_PARAM_RULES if opts.get("moe_ep")
                   else sharding.PARAM_RULES))
    params_sh = sharding.param_shardings(pspecs, mesh, rules)
    if sh["step"] == "prefill":
        batch = steps.input_specs(cfg, shape_name)
        batch_sh = steps.batch_shardings(cfg, shape_name, mesh)
        fn = steps.build_prefill_step(cfg)
        return fn, (params, batch), (params_sh, batch_sh)

    # decode
    B, S = sh["global_batch"], sh["seq_len"]
    batch = steps.input_specs(cfg, shape_name)
    batch_sh = steps.batch_shardings(cfg, shape_name, mesh)
    cache = registry.decode_state_specs(cfg, B, S)
    cache_sh = registry.decode_state_shardings(cfg, mesh, B, S)
    fn = steps.build_serve_step(cfg)
    return fn, (params, batch, cache), (params_sh, batch_sh, cache_sh)


def _grad_accum(arch: str, shape_name: str) -> int:
    """Microbatching so activations fit 16 GB/chip (batch 256 -> 8/pod-step)."""
    if shape_name != "train_4k":
        return 1
    # microbatch = 256/8 = 32 sequences: divisible by (pod*data) on both
    # meshes, and vocab-sharded logits stay ~100-300 MB/device.
    return 8


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             compress: str = "none", tag: str = "", opts: dict | None = None):
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    fn, args, shardings = build_cell(arch, shape_name, mesh, compress, opts)
    jitted = jax.jit(fn, in_shardings=shardings)
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll, counts = collective_bytes(hlo)

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "compress": compress,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops": float(cost.get("flops", -1)),
        "bytes_accessed": float(cost.get("bytes accessed", -1)),
        "memory": {
            k: int(getattr(mem, k, 0))
            for k in (
                "temp_size_in_bytes",
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "alias_size_in_bytes",
                "generated_code_size_in_bytes",
            )
        },
        "collective_bytes": coll,
        "collective_counts": counts,
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = ("_mp" if multi_pod else "") + (f"_{tag}" if tag else "")
    path = os.path.join(out_dir, f"{arch}_{shape_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    print(f"[dryrun] {arch} x {shape_name} ({record['mesh']}, compress={compress}): "
          f"compile {t_compile:.0f}s flops={record['flops']:.3e} "
          f"coll={sum(coll.values())/1e9:.2f}GB -> {path}")
    print(f"  memory: {record['memory']}")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--compress", default="none")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--msg-dtype", default="int32")
    ap.add_argument("--serve-resident", action="store_true")
    ap.add_argument("--serve-bf16", action="store_true")
    ap.add_argument("--gather-once", action="store_true")
    ap.add_argument("--moe-ep", action="store_true")
    args = ap.parse_args()
    opts = {"remat": args.remat, "accum": args.accum,
            "msg_dtype": args.msg_dtype,
            "serve_resident": args.serve_resident,
            "serve_bf16": args.serve_bf16,
            "gather_once": args.gather_once,
            "moe_ep": args.moe_ep}

    if args.all:
        ok, fail = 0, []
        for arch, shape_name, skip in configs.cells():
            try:
                run_cell(arch, shape_name, args.multi_pod, args.out, args.compress)
                ok += 1
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                fail.append((arch, shape_name, str(e)[:200]))
        print(f"[dryrun] {ok} cells OK, {len(fail)} failed")
        for f in fail:
            print("  FAIL:", f)
        raise SystemExit(1 if fail else 0)

    run_cell(args.arch, args.shape, args.multi_pod, args.out,
             args.compress, args.tag, opts)


if __name__ == "__main__":
    main()
