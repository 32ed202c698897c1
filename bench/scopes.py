"""Device time by the program's named scopes.

The program names its parts with ``jax.named_scope``: ``fl.forward``
around the loss, ``fl.optimizer`` around the update and ``fl.codec`` with
``draw``, ``dither``, ``encode``, ``psum`` and ``decode`` inside it around
the aggregation.  A scope reaches each HLO instruction's ``op_name``
metadata (``jit(step)/transpose(jvp(fl.forward))/while/body/...``), and
through it the device operations of a trace, which the ``XLA Ops`` line
names by the instruction's text alone.

``part`` maps an ``op_name`` to the part of the program it belongs to;
``self_ns`` gives each instant of a chip's busy time to the innermost
operation running, so that the parts' times add up to the busy time.
A ``ScopedTrace`` holds each operation's ``op_name`` beside ``ops``.

``bench/scope_report.py`` takes each event's ``op_name`` from the text of
the cell's compiled program, lowered again after the window
(``compiled_text``, ``hlo_op_names``): the profiler's ``XLA Ops`` events
carry no ``op_name`` among their stats on the chip.
"""
from __future__ import annotations

import heapq
import re
from dataclasses import dataclass, field

import devtrace

FORWARD, OPTIMIZER, CODEC = "fl.forward", "fl.optimizer", "fl.codec"
# the parts, in the order in which they claim an instruction
PARTS = ("codec", "optimizer", "remat", "backward", "forward")
CODEC_SCOPES = ("draw", "dither", "encode", "psum", "decode")
REMAT = "rematted_computation"
# what a transformation writes around a scope's name in a path component
WRAPPER = re.compile(r"(jvp|transpose|vmap|jit)\((.*)\)")


@dataclass
class ScopedTrace(devtrace.Trace):
    # the op_name of each operation of ``ops`` ("" where none is known)
    op_names: list = field(default_factory=list)

    def to_json(self):
        return {**super().to_json(), "op_names": self.op_names}

    @classmethod
    def from_json(cls, d):
        base = devtrace.Trace.from_json(d)
        return cls(base.ops, base.spans, list(d["op_names"]))


# ---------------------------------------------------------- classification
def _component(c: str):
    """A path component's scope name and the transformations around it:
    ``transpose(jvp(fl.forward))`` -> ("fl.forward", {"transpose", "jvp"})."""
    wrappers = set()
    while m := WRAPPER.fullmatch(c):
        wrappers.add(m.group(1))
        c = m.group(2)
    return c, wrappers


def _path_part(path: str):
    comps = [_component(c) for c in path.split("/")]
    names = [c for c, _w in comps]
    if CODEC in names:
        inner = names[names.index(CODEC) + 1:][:1]
        return "/".join(["codec"] + [c for c in inner if c in CODEC_SCOPES])
    if OPTIMIZER in names:
        return "optimizer"
    for i, (c, w) in enumerate(comps):
        if c == FORWARD and "transpose" in w:
            return "remat" if REMAT in names[i + 1:] else "backward"
    if FORWARD in names:
        return "forward"
    return None


def part(op_name: str):
    """The part of the program an instruction's ``op_name`` belongs to:
    ``codec`` (``codec/<scope>`` inside one of its scopes), ``optimizer``,
    ``remat`` (the recompute inside the backward), ``backward``,
    ``forward``, or None.  A fused instruction may carry several paths,
    joined by ``;``: the first part of ``PARTS`` that any of them meets
    wins."""
    found = [p for p in map(_path_part, op_name.split(";")) if p]
    if not found:
        return None
    return min(found, key=lambda p: PARTS.index(p.split("/")[0]))


def in_part(p, prefix: str) -> bool:
    return p is not None and (p == prefix or p.startswith(prefix + "/"))


# --------------------------------------------------------------- self time
def self_ns(intervals):
    """Self time of each (start, end) interval: every instant covered by
    any goes to the one that started last among those running then (the
    innermost, where they nest; the shorter on equal starts).  The self
    times add up to the length of the intervals' union."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    out = [0.0] * len(intervals)
    heap = []  # (-start, -rank, end, index) of the operations running
    t = None
    for rank, i in enumerate(order + [None]):
        s = intervals[i][0] if i is not None else float("inf")
        while heap and t < s:
            _ns, _nr, end, j = heap[0]
            if end <= t:
                heapq.heappop(heap)
                continue
            upto = min(end, s)
            out[j] += upto - t
            t = upto
        if i is not None:
            t = s
            heapq.heappush(heap, (-s, -rank, intervals[i][1], i))
    return out


def op_self_ns(tr: devtrace.Trace):
    """Self time inside the window of each operation of ``tr.ops``, each
    chip on its own; the self times of a chip add up to its busy time."""
    w0, w1 = tr.window()
    out = [0.0] * len(tr.ops)
    for chip in tr.chips():
        idx = [k for k, (c, _n, s, d) in enumerate(tr.ops)
               if c == chip and s < w1 and s + d > w0]
        iv = [(max(tr.ops[k][2], w0), min(tr.ops[k][2] + tr.ops[k][3], w1))
              for k in idx]
        for k, ns in zip(idx, self_ns(iv)):
            out[k] = ns
    return out


def part_seconds(tr: ScopedTrace):
    """Self time of each part in the window, averaged over chips;
    unscoped time under None."""
    parts = {}
    for name, ns in zip(tr.op_names, op_self_ns(tr)):
        p = part(name)
        parts[p] = parts.get(p, 0.0) + ns
    n = max(len(tr.chips()), 1)
    return {p: ns / n / 1e9 for p, ns in parts.items()}


def seconds_in(parts: dict, prefix: str) -> float:
    return sum(v for p, v in parts.items() if in_part(p, prefix))


# ------------------------------------------- instruction text to op_name
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*?)\s?"
                         r"([a-z][a-z0-9-]*)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")
OP_NAME = re.compile(r'metadata=\{op_name="((?:[^"\\]|\\.)*)"')


def head(text: str):
    """(name, shape, opcode) of an instruction's text, layouts left out
    (a trace event and the module's text print them alike, but need
    not), or None."""
    m = INSTRUCTION.match(text)
    if m is None:
        return None
    return m.group(1), LAYOUT.sub("", m.group(2)).strip(), m.group(3)


def hlo_op_names(hlo_text: str) -> dict:
    """{head: op_name} of every instruction of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        h = head(line)
        if h is not None:
            m = OP_NAME.search(line)
            out[h] = m.group(1) if m else ""
    return out


def with_op_names(tr: devtrace.Trace, table: dict) -> ScopedTrace:
    """The trace with each operation's op_name from ``table``; an event
    whose name, shape and opcode the program has not is of another
    program (the benchmark's own batch, a key) and has none."""
    names = [table.get(head(n), "") for _c, n, _s, _d in tr.ops]
    return ScopedTrace(tr.ops, tr.spans, names)


def compiled_text(r) -> str:
    """The compiled text of the program a cell's window runs, lowered again
    from the cell's own jitted function and arguments after the window
    (the persistent cache gives back the same executable)."""
    import jax

    import synthetic

    c = r.cell
    kind = r.traffic["kind"]
    if kind == "train":
        batch = c.batch(synthetic.step_key(c.key, c.next_step))
        lowered = c.step.lower(c.state, batch, c.codec_seed)
    elif kind == "codec":
        key = jax.random.fold_in(jax.random.fold_in(c.key, 1), 0)
        lowered = c.fn.lower(c.x, key)
    else:
        raise ValueError(f"no program to read scopes from in kind {kind!r}")
    return lowered.compile().as_text()

