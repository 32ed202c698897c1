"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: its entry in ``BENCHMARK.json``
(configuration, traffic, chips), ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` (which names the kind),
``bench/kinds/<kind>.py`` (set-up, window, check), ``bench/limits/<cell>.json``
(the limit of each compared number) and, for ``--trace 1``,
``bench/metrics/<metric>.py`` for each per-layer metric of the cell.

The run fails, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, and where the program is not beside the benchmark.
With ``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones, read from a device trace of the window.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import common  # noqa: E402
import devtrace  # noqa: E402

CLOCK = common.Clock()


class Context:
    def __init__(self, args, spec, entry, config, traffic):
        self.name = args.workload
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.spec, self.entry = spec, entry
        self.config, self.traffic = config, traffic
        self.chips = int(entry["chips"])


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec, cell):
    def applies(m):
        return cell in m["workloads"] if "workloads" in m else True

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (cell in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return e2e, per_layer


class Reading:
    """What a per-layer metric's reader gets: the window's trace and
    counts, the cell, the configuration's sizes and the chip's peaks."""

    def __init__(self, ctx, cell, window, tr, peaks):
        self.ctx, self.cell, self.window = ctx, cell, window
        self.trace, self.peaks = tr, peaks
        self.traffic = ctx.traffic
        self.sizes = common.dense_sizes(ctx.config)


def find_devices(chips: int):
    """The cell's chips, or None where JAX finds no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        common.log(f"bench: needs {chips} TPU chip(s), JAX found "
                   f"{len(devices)} {devices[0].platform!r} device(s)")
        return None
    return devices[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    common.use_checkout_cache()
    sys.path.insert(0, str(common.ROOT / "src"))
    try:
        spec = common.benchmark_spec()
        entry = common.workload_entry(spec, args.workload)
        config = common.config_file(spec, entry["config"])
        traffic = common.traffic_file(entry["traffic"])
        limits = common.read_json(common.BENCH / "limits"
                                  / f"{args.workload}.json")
        kind = load_module(common.BENCH / "kinds" / f"{traffic['kind']}.py",
                           f"kind_{traffic['kind']}")
        import repro  # noqa: F401  the system under test
    except (OSError, KeyError, ImportError) as e:
        common.log(f"bench: cannot set up {args.workload!r}: {e!r}")
        return 2

    import jax

    devices = find_devices(int(entry["chips"]))
    if devices is None:
        return 1
    peaks = common.peaks_for(devices[0].device_kind)
    compile_clock = common.CompileClock(jax.monitoring)
    ctx = Context(args, spec, entry, config, traffic)
    e2e, per_layer = cell_metrics(spec, args.workload)

    cell = kind.Cell(ctx)
    cell.setup()
    setup_s = CLOCK.now()
    common.log(f"bench: set-up {setup_s:.3f} s, of it compiling "
               f"{compile_clock.seconds:.3f} s (backend "
               f"{compile_clock.backend_seconds:.3f} s), cache hits "
               f"{compile_clock.cache_hits}, misses "
               f"{compile_clock.cache_misses}")
    compiles_before = compile_clock.cache_misses + compile_clock.cache_hits

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    if args.trace:
        span = jax.profiler.TraceAnnotation
        with devtrace.capture(tdir):
            with span(devtrace.WINDOW_SPAN):
                window = cell.window(ctx.seconds, span)
    else:
        window = cell.window(ctx.seconds,
                             lambda _name: contextlib.nullcontext())
    in_window = (compile_clock.cache_misses + compile_clock.cache_hits
                 - compiles_before)
    if in_window:
        common.log(f"bench: {in_window} compilations inside the window")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}

    if args.trace:
        tr = devtrace.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        reading = Reading(ctx, cell, window, tr, peaks)
        device["busy_s"] = devtrace.mean_busy_s(tr)
        device["window_s"] = devtrace.window_s(tr)
        metrics = {}
        for m in per_layer:
            reader = load_module(common.BENCH / "metrics" / f"{m['name']}.py",
                                 "metric_" + m["name"].replace(".", "_"))
            value = reader.read(reading)
            if value is None:
                common.log(f"bench: {m['name']} found nothing to read in "
                           f"{args.workload}'s trace")
                return 3
            metrics[m["name"]] = common.metric(value, m["unit"])
        breakdown = {"device_ops": devtrace.top_ops(tr),
                     "idle_gaps": devtrace.idle_gaps(tr)}
        del tr, reading
    else:
        produced = cell.end_to_end(window)
        metrics = {"setup_s": common.metric(setup_s, "s")}
        for m in e2e:
            if m["name"] != "setup_s":
                metrics[m["name"]] = produced[m["name"]]
        breakdown = None
    common.log(f"bench: window {window}")

    cell.free()
    values = cell.check()
    checks = {}
    correct = window["failed"] == 0
    for name, value in values.items():
        limit = limits.get(name)
        ok = limit is not None and common.finite(value) and value <= limit
        correct = correct and ok
        checks[name] = {"value": value, "limit": limit}
    common.print_result(correct, window["attempted"], window["failed"],
                        metrics, device, checks, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
