"""Readings that the limits of ``bench/limits/<cell>.json`` are set from:
the sound program, the control and the planted faults, on several seeds in
one process.  The benchmark's own runs never run this.

    python bench/control.py --workload <cell> --seeds 11,12,13 \
        [--only program,control,faults] [--out <file>.jsonl]

Training cells: the program's set-up (step 1, no window) against the
float32 reference; the control is the reference with every matmul
operand in float8_e4m3fn (``reference.py``) put in the program's place,
with its own N(0, sigma^2) draw; the fault planted in the reference put
in the program's place is half of the batch left out.  A state returned
unchanged reads 1 on ``row_weight_gap`` and ``update_norm_gap`` without
a run.

Codec cells: the program's set-up and a window of ``min_calls`` calls;
the control is the program's own per-tensor shared randomness, which keeps
each coordinate's law but not their independence.  The faults are planted
in the program by ``tests/test_faults.py`` at a small size.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import common  # noqa: E402
import run  # noqa: E402


def train_readings(kind, ctx, only):
    cell = kind.Cell(ctx)
    out = {}
    t = time.perf_counter()
    cell.setup()
    cell.free()
    out["setup_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ref = cell.reference_rows()
    out["reference_s"] = time.perf_counter() - t
    out["loss"] = {"program": cell.prog["loss"], "reference": ref["loss"]}
    out["gram"] = ref["gram"].tolist()
    sides = []
    if "program" in only:
        sides.append(("program", cell.program_side))
    if "control" in only:
        sides.append(("control", lambda: cell.side(matmul="fp8")))
    if "faults" in only:
        sides.append(("half_batch",
                      lambda: cell.side(rows=range(cell.rows // 2))))
    for name, make in sides:
        side = make()
        out[name] = {**cell.compare(side, ref), "loss": side["loss"]}
        del side
        gc.collect()
    return out


def codec_readings(kind, ctx, only):
    out = {}
    runs = []
    if "program" in only:
        runs.append(("program", {}))
    if "control" in only:
        runs.append(("control", {"per_coord": False}))
    for name, kw in runs:
        cell = kind.Cell(ctx, **kw)
        cell.setup()
        cell.window(0.0, lambda _n: contextlib.nullcontext())
        cell.free()
        out[name] = cell.readings()
        del cell
        gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--only", default="program,control,faults")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    common.use_checkout_cache()
    sys.path.insert(0, str(common.ROOT / "src"))
    spec = common.benchmark_spec()
    entry = common.workload_entry(spec, args.workload)
    config = common.config_file(spec, entry["config"])
    traffic = common.traffic_file(entry["traffic"])
    kind = run.load_module(common.BENCH / "kinds" / f"{traffic['kind']}.py",
                           f"kind_{traffic['kind']}")
    devices = run.find_devices(int(entry["chips"]))
    if devices is None:
        return 1
    only = set(args.only.split(","))
    readings = train_readings if traffic["kind"] == "train" else codec_readings
    sink = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        ns = argparse.Namespace(workload=args.workload, seed=seed, seconds=0)
        ctx = run.Context(ns, spec, entry, config, traffic)
        t = time.perf_counter()
        got = readings(kind, ctx, only)
        line = {"workload": args.workload, "seed": seed,
                "seconds": time.perf_counter() - t,
                "device": devices[0].device_kind, **got}
        print(json.dumps(line), flush=True)
        if sink:
            sink.write(json.dumps(line) + "\n")
            sink.flush()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
