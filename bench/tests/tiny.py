"""Drive ``bench/run.py`` on the CPU at a tiny size: the harness's look for
a chip is skipped and the configuration, traffic and limits are small
stand-ins, so that every other part of a run (set-up, window, trace-free
metrics, the reference and the comparison) is exercised."""
from __future__ import annotations

import contextlib
import io
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import common  # noqa: E402
import run  # noqa: E402

CONFIGS = {
    "qwen1.5-0.5b": {
        "arch": "qwen1.5-0.5b", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "vocab_size": 256, "hidden_act": "silu",
        "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
        "tie_word_embeddings": True, "qkv_bias": True, "norm": "rmsnorm"},
    "starcoder2-3b": {
        "arch": "starcoder2-3b", "hidden_size": 64, "intermediate_size": 256,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 256,
        "hidden_act": "gelu_pytorch_tanh", "norm_epsilon": 1e-5,
        "norm": "layernorm", "rope_theta": 999999.4420358813,
        "tie_word_embeddings": True, "use_bias": True},
}


def traffic(name):
    t = common.read_json(BENCH / "traffic" / f"{name}.json")
    if t["kind"] == "train":
        t.update(batch=2, seq_len=256)
    return t


@contextlib.contextmanager
def tiny_size(limits=None):
    """Every cell at the tiny size on the CPU devices, with ``limits`` in
    place of the limit files."""
    spec = common.benchmark_spec()
    patches = {
        (common, "benchmark_spec"): lambda: spec,
        (common, "config_file"): lambda _s, c: CONFIGS[c],
        (common, "traffic_file"): lambda t: traffic(t),
        (common, "read_json"): _limits_or(limits or {}, common.read_json),
        (run, "find_devices"): _cpu_devices,
        # arithmetic only: no number of a CPU run is a device number
        (common, "peaks_for"): lambda _kind: _PEAKS["TPU v5 lite"],
    }
    saved = {k: getattr(*k) for k in patches}
    try:
        for (mod, attr), fn in patches.items():
            setattr(mod, attr, fn)
        yield
    finally:
        for (mod, attr), fn in saved.items():
            setattr(mod, attr, fn)


def run_cell(name, limits, seed=7, seconds=0.5, trace=0):
    """One run of cell ``name`` at the tiny size; returns the result line
    as a dict (None if none was printed) and the exit code."""
    out = io.StringIO()
    with tiny_size(limits), contextlib.redirect_stdout(out):
        rc = run.main(["--workload", name, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)])
    lines = out.getvalue().strip().splitlines()
    return (json.loads(lines[-1]) if lines else None), rc


_PEAKS = common.read_json(BENCH / "peaks.json")


def _limits_or(limits, read_json):
    def f(path):
        return dict(limits) if Path(path).parent.name == "limits" else read_json(path)
    return f


def _cpu_devices(chips):
    import jax

    devices = jax.devices()
    return devices[:chips] if len(devices) >= chips else None
