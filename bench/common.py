"""Pieces every cell of the benchmark shares: where the checkout is, the
compile cache, the device check, the configuration files, the compile
clock and the result line.

Nothing here imports the program at module level: ``bench/run.py`` must
be able to say that the program is missing and exit non-zero.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time
import zlib
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def use_checkout_cache():
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, set before jax is imported so that the program, which defers
    to ``JAX_COMPILATION_CACHE_DIR``, keeps its programs there too."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"


def read_json(path: Path):
    with open(path) as f:
        return json.load(f)


def benchmark_spec():
    return read_json(ROOT / "BENCHMARK.json")


def workload_entry(spec, name: str):
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_file(spec, config_name: str):
    for c in spec["configs"]:
        if c["name"] == config_name:
            return read_json(ROOT / c["file"])
    raise KeyError(f"no config {config_name!r} in BENCHMARK.json")


def traffic_file(traffic: str):
    return read_json(BENCH / "traffic" / f"{traffic}.json")


def peaks_for(device_kind: str):
    table = read_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; add them with their source")
    return table[device_kind]


def seed_key(seed: int):
    """A PRNG key for any non-negative whole number, wider than 32 bits
    included: the low and high words are folded in one after the other."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def name_key(key, name: str):
    import jax

    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


# ------------------------------------------------------- model configuration
_ACTS = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}
_EPS = {"rmsnorm": ("rms_norm_eps", 1e-6), "layernorm": ("norm_epsilon", 1e-5)}


def dense_sizes(c: dict) -> dict:
    """The sizes of a dense decoder configuration file, in plain names
    that the reference and the counts share."""
    d, hq = c["hidden_size"], c["num_attention_heads"]
    return {
        "layers": c["num_hidden_layers"], "d": d, "ff": c["intermediate_size"],
        "hq": hq, "hk": c["num_key_value_heads"], "hd": d // hq,
        "vocab": c["vocab_size"], "theta": float(c["rope_theta"]),
        "norm": c["norm"], "eps": float(c[_EPS[c["norm"]][0]]),
        "act": _ACTS[c["hidden_act"]],
        "qkv_bias": bool(c.get("qkv_bias", c.get("use_bias", False))),
        "mlp_bias": bool(c.get("use_bias", False)),
        "tied": bool(c["tie_word_embeddings"]),
    }


def program_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file: the repo's
    entry for ``arch`` with every size the file states put in."""
    from repro import configs

    s = dense_sizes(c)
    base = configs.get_config(c["arch"])
    if base.kind != "dense":
        raise ValueError(f"{c['arch']}: only dense decoders are benchmarked")
    if s["eps"] != _EPS[s["norm"]][1]:
        raise ValueError(f"{c['arch']}: the program's {s['norm']} eps is "
                         f"{_EPS[s['norm']][1]}, the file states {s['eps']}")
    if s["mlp_bias"] != (s["act"] == "gelu"):
        raise ValueError(f"{c['arch']}: the program puts MLP biases only on "
                         f"the gelu MLP")
    return base.scaled(
        n_layers=s["layers"], d_model=s["d"], d_ff=s["ff"], n_heads=s["hq"],
        n_kv_heads=s["hk"], vocab=s["vocab"], rope_theta=s["theta"],
        norm=s["norm"], act=s["act"], qkv_bias=s["qkv_bias"],
        tie_embeddings=s["tied"], head_dim=None, window=None)


# ------------------------------------------------------------ compile clock
class CompileClock:
    """Seconds jax spent tracing, lowering and compiling, and persistent
    cache hits and misses, read from jax's own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.backend_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[-1]:
            self.backend_seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


# ------------------------------------------------------------------ result
def metric(value, unit):
    return {"value": value, "unit": unit}


def finite(x) -> bool:
    return x is not None and math.isfinite(x)


class Clock:
    def __init__(self):
        self.t0 = time.perf_counter()

    def now(self):
        return time.perf_counter() - self.t0


def print_result(correct, attempted, failed, metrics, device, checks,
                 breakdown=None):
    """The last line of stdout, and every compared number beside its limit
    as the last lines of stderr; ``checks`` goes last in the line."""
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    print(json.dumps(line), flush=True)
