"""Device time by named scope (``bench/scopes.py``): the classification of
op_names, the self-time partition, the op_names read from a compiled
program, and the recorded traces of both cells."""
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import common
import devtrace
import run
import scopes

DATA = Path(__file__).resolve().parent / "data"
FWD = "jit(step)/jvp(fl.forward)/while/body/closed_call/dot_general"
BWD = "jit(step)/transpose(jvp(fl.forward))/while/body/dot_general"
REMAT = ("jit(step)/transpose(jvp(fl.forward))/jvp(fl.forward)/while/body/"
         "checkpoint/rematted_computation/dot_general")


@pytest.mark.parametrize("op_name,part", [
    (FWD, "forward"),
    (BWD, "backward"),
    (REMAT, "remat"),
    ("jit(step)/vmap(transpose(jvp(fl.forward)))/while/body/mul", "backward"),
    ("jit(step)/vmap(jvp(fl.forward))/while/body/mul", "forward"),
    ("jit(step)/fl.optimizer/mul", "optimizer"),
    ("jit(step)/fl.codec/draw/vmap(jit(_interp))/jit(searchsorted)/while",
     "codec/draw"),
    ("jit(step)/shard_map/fl.codec/psum/psum", "codec/psum"),
    ("jit(<lambda>)/fl.codec/jit(_threefry_split)/_compress_leaf/while",
     "codec"),
    ("jit(step)/fl.codec", "codec"),
    # the first rule that any path of a fused instruction meets wins
    (BWD + ";jit(step)/fl.optimizer/add", "optimizer"),
    (FWD + ";" + REMAT, "remat"),
    ("jit(step)/fl.optimizer/add;jit(step)/fl.codec/decode/mul",
     "codec/decode"),
    ("jit(step)/add", None),
    ("", None),
    ("jit(step)/jvp(fl.forwards)/mul", None),
])
def test_part_of_op_name(op_name, part):
    assert scopes.part(op_name) == part


def test_self_time_of_nested_and_overlapping_ops():
    # a while [0, 100) with body ops [10, 30) and [30, 50); an op [90, 120)
    # that starts inside the while and outlives it; an op [200, 210) alone
    iv = [(0, 100), (10, 30), (30, 50), (90, 120), (200, 210)]
    assert scopes.self_ns(iv) == [50, 20, 20, 30, 10]
    # equal starts: the shorter is the inner one
    assert scopes.self_ns([(0, 10), (0, 4)]) == [6, 4]


def hand_trace():
    # window 0..100 on two chips; chip 0 nests a backward op in a forward
    # while, and its last op outlives the window
    ops = [(0, "%while.1 = f32[] while(f32[] %p)", 0, 60),
           (0, "%fusion.2 = f32[] fusion(f32[] %p)", 10, 20),
           (0, "%fusion.3 = f32[] fusion(f32[] %p)", 70, 10),
           (0, "%copy.4 = f32[] copy(f32[] %p)", 90, 30),
           (1, "%fusion.3 = f32[] fusion(f32[] %p)", 0, 50),
           (1, "%fusion.5 = f32[] fusion(f32[] %p)", 60, 20)]
    names = [FWD, BWD, "jit(step)/fl.optimizer/add", "",
             "jit(step)/fl.optimizer/add", REMAT]
    spans = [("bench.window", 0, 100)]
    return scopes.ScopedTrace(ops, spans, names)


def test_parts_add_up_to_busy_time():
    tr = hand_trace()
    parts = scopes.part_seconds(tr)
    # chip 0: forward 40, backward 20, optimizer 10, unscoped 10 (clipped);
    # chip 1: optimizer 50, remat 20; averaged over the two chips
    assert parts == pytest.approx({"forward": 20e-9, "backward": 10e-9,
                                   "optimizer": 30e-9, "remat": 10e-9,
                                   None: 5e-9})
    assert sum(parts.values()) == pytest.approx(devtrace.mean_busy_s(tr))
    assert scopes.seconds_in(parts, "optimizer") == pytest.approx(30e-9)


def test_json_round_trip_keeps_op_names():
    tr = hand_trace()
    back = scopes.ScopedTrace.from_json(json.loads(json.dumps(tr.to_json())))
    assert back == tr


# ------------------------------------------------- the compiled program
def test_op_names_from_a_compiled_program():
    def f(x):
        def loss(p):
            with jax.named_scope("fl.forward"):
                return jnp.sum(jax.checkpoint(lambda q: jnp.tanh(q @ q))(p))

        g = jax.grad(loss)(x)
        with jax.named_scope("fl.optimizer"):
            return x - 0.1 * g

    text = jax.jit(f).lower(jnp.ones((8, 8))).compile().as_text()
    table = scopes.hlo_op_names(text)
    # a trace names an event by the instruction's text without metadata
    events = [line.split(", metadata=")[0].strip().removeprefix("ROOT ")
              for line in text.splitlines()
              if " = " in line and "metadata=" in line
              and "parameter(" not in line]
    assert events
    tr = devtrace.Trace([(0, e, 10 * k, 10) for k, e in enumerate(events)],
                        [("bench.window", 0, 10 * len(events))])
    st = scopes.with_op_names(tr, table)
    assert all(st.op_names)
    assert {scopes.part(n) for n in st.op_names} >= {"remat", "optimizer"}


def test_event_of_another_program_has_no_op_name():
    table = scopes.hlo_op_names(
        '  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'calls=%c, metadata={op_name="jit(step)/fl.optimizer/add"}')
    same = "%fusion.3 = f32[8]{0:T(256)} fusion(f32[8]{0:T(256)} %p)"
    other = "%fusion.3 = s32[2,2]{1,0} fusion(s32[2]{0} %q)"
    st = scopes.with_op_names(devtrace.Trace(
        [(0, same, 0, 1), (0, other, 1, 1)], [("bench.window", 0, 2)]),
        table)
    assert st.op_names == ["jit(step)/fl.optimizer/add", ""]


def test_head_of_a_recorded_event():
    tr = devtrace.Trace.from_json(json.loads(
        (DATA / "train_trace_slice.json").read_text()))
    heads = [scopes.head(n) for _c, n, _s, _d in tr.ops]
    assert heads[0] == ("broadcast.850", "f32[589824,2,128]", "broadcast")
    assert heads[4] == ("broadcast_multiply_fusion.8",
                        "(f32[589824,2,128], f32[589824,2,128])", "fusion")
    assert heads[1][2] == "custom-call"


# ------------------------------------ the recorded unscoped trace slice
def stand_in_reading(tr):
    # a stand-in window: it pins each reader's arithmetic on the slice,
    # which holds one leaf's kernels and not a whole step
    cfg = common.config_file(common.benchmark_spec(), "starcoder2-3b")
    return SimpleNamespace(
        trace=tr, traffic=common.traffic_file("tensor"),
        window={"steps": 4, "calls": 1, "coords": 2**20, "tokens": 8192,
                "elapsed_s": 0.5},
        peaks=common.peaks_for("TPU v5 lite"), sizes=common.dense_sizes(cfg),
        cell=SimpleNamespace(coords=12_850_176), ctx=SimpleNamespace(chips=1))


EXISTING = {
    "device_idle_share.train": 4.188394661097661e-05,
    "device_idle_share.codec": 4.188394661097661e-05,
    "train_mfu": 27.941882652328935,
    "codec_kernel_roofline.train": 403.6989405818329,
    "codec_call_roofline.codec": 1.5771906551595811,
    "draw_ms_per_mcoord.codec": 4.172121000000001,
}


@pytest.mark.parametrize("metric", sorted(EXISTING))
def test_existing_readers_read_the_recorded_slice_as_before(metric):
    tr = devtrace.Trace.from_json(json.loads(
        (DATA / "train_trace_slice.json").read_text()))
    reader = run.load_module(common.BENCH / "metrics" / f"{metric}.py",
                             "metric_" + metric.replace(".", "_"))
    assert reader.read(stand_in_reading(tr)) == pytest.approx(
        EXISTING[metric], rel=1e-12)


def test_breakdown_of_the_recorded_slice_as_before():
    tr = devtrace.Trace.from_json(json.loads(
        (DATA / "train_trace_slice.json").read_text()))
    assert devtrace.top_ops(tr) == [
        ["_fused_decode_percoord.18", 0.003963972],
        ["_fused_encode_percoord.18", 0.00380165],
        ["broadcast_multiply_fusion.8", 0.002808672],
        ["broadcast.860", 0.001122123],
        ["reshape.753", 0.000191326],
        ["broadcast.850", 5e-05]]
    assert devtrace.idle_gaps(tr) == [["bench.wait", 2e-09]] + [
        ["bench.wait", 1e-09]] * 3


# ------------------------------------------- the recorded scoped slices
SLICES = {
    "train_scoped_slice.json": {"forward", "backward", "remat", "optimizer",
                                "codec", "codec/draw", "codec/dither",
                                "codec/encode", "codec/decode"},
    "codec_scoped_slice.json": {"codec", "codec/draw", "codec/dither",
                                "codec/encode", "codec/decode"},
}


def recorded(name):
    return scopes.ScopedTrace.from_json(json.loads((DATA / name).read_text()))


@pytest.mark.parametrize("name", sorted(SLICES))
def test_recorded_slice_holds_every_part(name):
    tr = recorded(name)
    assert {scopes.part(n) for n in tr.op_names} - {None} == SLICES[name]
    assert all(scopes.head(n) is not None for _c, n, _s, _d in tr.ops)
    parts = scopes.part_seconds(tr)
    assert sum(parts.values()) == pytest.approx(devtrace.mean_busy_s(tr),
                                                rel=1e-12)
    # the kernels run inside the encode and decode scopes
    kernels = {scopes.part(m) for (_c, n, _s, _d), m
               in zip(tr.ops, tr.op_names) if devtrace.is_codec_kernel(n)}
    assert kernels and kernels <= {"codec/encode", "codec/decode"}


@pytest.mark.parametrize("name", sorted(SLICES))
def test_recorded_while_keeps_only_its_own_time(name):
    tr = recorded(name)
    selfs = scopes.op_self_ns(tr)
    loops = [k for k, (_c, n, _s, _d) in enumerate(tr.ops)
             if scopes.head(n)[2] == "while"]
    nested = 0
    for k in loops:
        s0, e0 = tr.ops[k][2], tr.ops[k][2] + tr.ops[k][3]
        inside = [j for j, (_c, _n, s, d) in enumerate(tr.ops)
                  if j != k and s >= s0 and s + d <= e0]
        nested += bool(inside)
        covered = devtrace.merged([(tr.ops[j][2], tr.ops[j][2] + tr.ops[j][3])
                                   for j in inside])
        assert selfs[k] == tr.ops[k][3] - sum(e - s for s, e in covered)
    assert nested


def test_recorded_recompute_sits_inside_the_transpose():
    tr = recorded("train_scoped_slice.json")
    remat = [n for n in tr.op_names if scopes.part(n) == "remat"]
    assert remat and all("transpose(jvp(fl.forward))" in n
                         and "rematted_computation" in n for n in remat)


@pytest.mark.parametrize("name", sorted(SLICES))
def test_recorded_scoped_slice_loads_as_a_plain_trace(name):
    d = json.loads((DATA / name).read_text())
    tr = devtrace.Trace.from_json(d)
    assert len(tr.ops) == len(d["op_names"]) and tr.window()
