"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``us_per_call`` is the
benchmark's primary value (bits, MSE, entropy, seconds — stated in the
``derived`` column); each module's docstring maps it to the paper
artifact it reproduces (see DESIGN.md §6).

    PYTHONPATH=src python -m benchmarks.run [--only fig2,fig4]
"""
from __future__ import annotations

import argparse
import sys
import time


def _csv_printer():
    def emit(name: str, value, derived: str = ""):
        print(f"{name},{value},{derived}")

    return emit


MODULES = [
    "fig2_entropy",
    "fig4_comm_cost",
    "fig5_sigm_csgm",
    "fig6_ddg",
    "fig10_langevin",
    "table1_properties",
    "bench_runtime",
    "bench_compress",
    "bench_serve",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated module prefixes")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else None

    emit = _csv_printer()
    print("name,us_per_call,derived")
    failures = []
    for name in MODULES:
        if only and not any(name.startswith(o) for o in only):
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            mod.run(emit)
            print(f"# {name}: done in {time.time() - t0:.1f}s", file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            print(f"# {name}: FAILED {e!r}", file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
