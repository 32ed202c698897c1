"""Device trace of a run's window, and the reductions every per-layer
metric shares.

``capture`` records the window with the JAX profiler; ``load`` reads the
``.xplane.pb`` it wrote into a ``Trace``: the device operations of each
chip (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane) and the
benchmark's own host spans (``bench.*``), all in nanoseconds on the
profiler's clock.  A ``Trace`` round-trips through plain JSON
(``to_json`` / ``from_json``), so a recorded trace can be kept as a test
fixture.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"


@dataclass
class Trace:
    # (chip, name, start_ns, duration_ns) of every device operation
    ops: list = field(default_factory=list)
    # (name, start_ns, duration_ns) of every bench.* host span
    spans: list = field(default_factory=list)

    def window(self):
        w = [s for s in self.spans if s[0] == WINDOW_SPAN]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
        return w[0][1], w[0][1] + w[0][2]

    def chips(self):
        return sorted({o[0] for o in self.ops})

    def to_json(self):
        return {"ops": self.ops, "spans": self.spans}

    @classmethod
    def from_json(cls, d):
        return cls([tuple(o) for o in d["ops"]], [tuple(s) for s in d["spans"]])


@contextlib.contextmanager
def capture(directory: str):
    import jax

    jax.profiler.start_trace(directory)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(directory: str) -> Trace:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} xplane files under {directory}")
    data = ProfileData.from_file(files[0])
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                chip = int(m.group(1))
                tr.ops.extend((chip, e.name, e.start_ns, e.duration_ns)
                              for e in line.events)
            elif plane.name.startswith("/host:"):
                tr.spans.extend((e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name.startswith("bench."))
    return tr


# ------------------------------------------------------------- reductions
def op_name(name: str) -> str:
    """The HLO instruction's own name: on the TPU an event of the ``XLA
    Ops`` line is named by the instruction's whole text
    (``%fusion.12 = f32[...] fusion(... %operand.3 ...)``), whose operands
    name other instructions."""
    return name.split(" = ", 1)[0].lstrip("%")


def merged(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(tr: Trace, chip: int, pred=None):
    """Merged device-busy intervals of one chip inside the window, for the
    operations ``pred(name)`` accepts (all by default)."""
    w0, w1 = tr.window()
    iv = [(max(s, w0), min(s + d, w1)) for c, n, s, d in tr.ops
          if c == chip and s < w1 and s + d > w0 and (pred is None or pred(n))]
    return merged(iv)


def busy_ns(tr: Trace, chip: int, pred=None) -> float:
    return float(sum(e - s for s, e in busy_intervals(tr, chip, pred)))


def mean_busy_s(tr: Trace, pred=None) -> float:
    chips = tr.chips()
    if not chips:
        return 0.0
    return sum(busy_ns(tr, c, pred) for c in chips) / len(chips) / 1e9


def window_s(tr: Trace) -> float:
    w0, w1 = tr.window()
    return (w1 - w0) / 1e9


def op_seconds(tr: Trace, pred, chip=None) -> float:
    """Summed device time of the operations ``pred(name)`` accepts inside
    the window, averaged over chips unless ``chip`` is given."""
    chips = [chip] if chip is not None else tr.chips()
    return sum(busy_ns(tr, c, pred) for c in chips) / max(len(chips), 1) / 1e9


def top_ops(tr: Trace, n: int = 10, chip: int = 0):
    """The ``n`` instructions with the most device time in the window; an
    instruction of a step runs under the same name in every step."""
    w0, w1 = tr.window()
    tot = {}
    for c, name, s, d in tr.ops:
        if c == chip and s < w1 and s + d > w0:
            k = op_name(name)
            tot[k] = tot.get(k, 0) + min(s + d, w1) - max(s, w0)
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10, chip: int = 0):
    """The longest idle gaps of one chip inside the window, each named by
    the bench.* host span (they do not nest) that covers its middle."""
    w0, w1 = tr.window()
    busy = busy_intervals(tr, chip)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    spans = sorted((s for s in tr.spans if s[0] != WINDOW_SPAN),
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]
    gaps = []
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            mid = (s + e) / 2
            i = bisect.bisect_right(starts, mid) - 1
            label = "outside spans"
            if i >= 0 and mid <= spans[i][1] + spans[i][2]:
                label = spans[i][0]
            gaps.append([label, (e - s) / 1e9])
    return sorted(gaps, key=lambda g: -g[1])[:n]


# ------------------------------------------------- names of what is read
# the program's two fused codec kernels (kernels/fused_agg.py): their
# custom calls take the names of the jitted wrappers in kernels/ops.py
# (_fused_encode_percoord, _fused_decode_scalar, ...) or, named by the
# kernels themselves, _encode_kernel and _decode_kernel
CODEC_KERNELS = re.compile(r"_fused_(en|de)code_|_(en|de)code_kernel")


def is_codec_kernel(name: str) -> bool:
    return bool(CODEC_KERNELS.search(op_name(name)))

