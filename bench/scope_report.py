"""Where a cell's device time goes, by the program's named scopes.

    python bench/scope_report.py --workload <cell> --seed <n> --seconds <s>
        [--inner <part>] [--slice <file.json>]

Runs the cell's set-up and one traced window as ``bench/run.py --trace 1``
does, then reduces the trace by ``bench/scopes.py``: self time of each part
per unit of the window's work (a step, or 2^20 coordinates), the unscoped
instructions, the codec's self time outside its two kernels and, with
``--inner``, the self time inside one part (``codec/draw``, ``codec``, ...)
by the innermost jitted function and operation of each op_name
(``searchsorted/while``, ``_uniform/max``).  It also says how long each
reading takes.  ``--slice`` writes a few operations of each part, a nested
``while`` among them, as a ``ScopedTrace`` fixture for ``bench/tests``.
The summary is the last line of stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import common  # noqa: E402
import devtrace  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402

JITTED = re.compile(r"jit\(([^()]*)\)")


def inner_name(op_name: str) -> str:
    """The innermost jitted function below the program's own ``jit`` and
    the operation, of an op_name's first path:
    ``jit(f)/fl.codec/draw/vmap(jit(_interp))/jit(searchsorted)/while`` ->
    ``searchsorted/while``; ``-`` where no function is jitted inside."""
    path = op_name.split(";")[0].split("/")
    fns = [m.group(1) for c in path[1:] for m in [JITTED.search(c)] if m]
    return f"{fns[-1] if fns else '-'}/{path[-1]}"


def top(totals: dict, n: int):
    return [[k, v / 1e9] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def pick_slice(st: scopes.ScopedTrace, selfs, per_part: int = 3,
               nested: int = 4):
    """Indices of a few operations of each part on chip 0: the first
    ``per_part`` of each that are no ``while``, and the part's longest
    ``while`` with the first ``nested`` operations inside it."""
    w0, w1 = st.window()
    chip0 = [k for k, (c, _n, s, d) in enumerate(st.ops)
             if c == 0 and s >= w0 and s + d <= w1]
    by_part = {}
    for k in chip0:
        by_part.setdefault(scopes.part(st.op_names[k]), []).append(k)

    def is_while(k):
        h = scopes.head(st.ops[k][1])
        return h is not None and h[2] == "while"

    keep = set()
    for ks in by_part.values():
        keep.update([k for k in ks if not is_while(k) and selfs[k] > 0]
                    [:per_part])
        loops = [k for k in ks if is_while(k)]
        if loops:
            lp = max(loops, key=lambda k: st.ops[k][3])
            s0, e0 = st.ops[lp][2], st.ops[lp][2] + st.ops[lp][3]
            inside = [k for k in chip0 if k != lp and st.ops[k][2] >= s0
                      and st.ops[k][2] + st.ops[k][3] <= e0]
            keep.add(lp)
            keep.update(inside[:nested])
    return sorted(keep, key=lambda k: st.ops[k][2])


def write_slice(path: str, st: scopes.ScopedTrace, idx, source: str):
    ops = [st.ops[k] for k in idx]
    t0 = min(o[2] for o in ops)
    t1 = max(o[2] + o[3] for o in ops)
    sl = scopes.ScopedTrace(ops, [(devtrace.WINDOW_SPAN, t0, t1 - t0)],
                            [st.op_names[k] for k in idx])
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"source": source, **sl.to_json()}, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--inner", default=None)
    ap.add_argument("--slice", default=None)
    args = ap.parse_args(argv)

    common.use_checkout_cache()
    sys.path.insert(0, str(common.ROOT / "src"))
    spec = common.benchmark_spec()
    entry = common.workload_entry(spec, args.workload)
    config = common.config_file(spec, entry["config"])
    traffic = common.traffic_file(entry["traffic"])
    kind = run.load_module(common.BENCH / "kinds" / f"{traffic['kind']}.py",
                           f"kind_{traffic['kind']}")
    import jax

    devices = run.find_devices(int(entry["chips"]))
    if devices is None:
        return 1
    ctx = run.Context(args, spec, entry, config, traffic)
    cell = kind.Cell(ctx)
    cell.setup()
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    with devtrace.capture(tdir):
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            window = cell.window(ctx.seconds, jax.profiler.TraceAnnotation)

    t0 = time.perf_counter()
    tr = devtrace.load(tdir)
    t_load = time.perf_counter() - t0
    shutil.rmtree(tdir, ignore_errors=True)
    reading = run.Reading(ctx, cell, window, tr,
                          common.peaks_for(devices[0].device_kind))
    t0 = time.perf_counter()
    table = scopes.hlo_op_names(scopes.compiled_text(reading))
    t_text = time.perf_counter() - t0
    t0 = time.perf_counter()
    st = scopes.with_op_names(tr, table)
    selfs = scopes.op_self_ns(st)
    parts = scopes.part_seconds(st)
    t_self = time.perf_counter() - t0

    units = (window["steps"] if "steps" in window
             else window["coords"] / 2**20)
    busy = devtrace.mean_busy_s(tr)
    unscoped, inner, kernels = {}, {}, 0.0
    for (_c, name, _s, _d), n, ns in zip(st.ops, st.op_names, selfs):
        p = scopes.part(n)
        if p is None:
            key = devtrace.op_name(name) + "  " + (n or "<no op_name>")
            unscoped[key] = unscoped.get(key, 0.0) + ns
        elif scopes.in_part(p, "codec") and devtrace.is_codec_kernel(name):
            kernels += ns
        if args.inner and scopes.in_part(p, args.inner):
            key = inner_name(n)
            inner[key] = inner.get(key, 0.0) + ns
    n_chips = max(len(tr.chips()), 1)
    summary = {
        "workload": args.workload, "window": window, "busy_s": busy,
        "unit": "step" if "steps" in window else "2^20 coords",
        "ms_per_unit": {str(p): 1000.0 * v / units for p, v in
                        sorted(parts.items(), key=lambda kv: -kv[1])},
        "unscoped_share": parts.get(None, 0.0) / busy if busy else None,
        "codec_outside_kernels_ms_per_unit": 1000.0 * (
            scopes.seconds_in(parts, "codec")
            - kernels / n_chips / 1e9) / units,
        "unscoped_top": top(unscoped, 15),
        "inner_top": top(inner, 25),
        "op_name_share": sum(map(bool, st.op_names)) / max(len(st.ops), 1),
        "read_s": {"devtrace_load": t_load, "compiled_text": t_text,
                   "self_times": t_self},
    }
    if args.slice:
        write_slice(args.slice, st, pick_slice(st, selfs),
                    f"a few operations of each part, a nested while among "
                    f"them, from the traced window of bench/scope_report.py "
                    f"--workload {args.workload} on one "
                    f"{devices[0].device_kind}; op_names read from the "
                    f"compiled program; the bench.window span is cut to "
                    f"the slice")
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
