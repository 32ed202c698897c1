"""The reduction from a device trace to the per-layer metrics."""
import json
from pathlib import Path

import pytest

import devtrace

RECORDED = Path(__file__).resolve().parent / "data" / "train_trace_slice.json"


def hand_trace():
    # window 0..100 ns on two chips; chip 0 has nested and overlapping ops
    ops = [(0, "fusion.1", 10, 20), (0, "_fused_encode_percoord.3", 25, 10),
           (0, "all-reduce.7", 50, 10), (0, "fusion.1", 95, 20),
           (1, "fusion.2", 0, 50), (1, "all-reduce.7", 60, 20)]
    spans = [("bench.window", 0, 100), ("bench.step", 0, 40),
             ("bench.wait", 40, 60)]
    return devtrace.Trace(ops, spans)


def test_busy_union_and_clipping_by_hand():
    tr = hand_trace()
    # chip 0: [10, 35) and [50, 60) and [95, 100) -> 25 + 10 + 5
    assert devtrace.busy_ns(tr, 0) == 40
    # chip 1: [0, 50) and [60, 80) -> 70
    assert devtrace.busy_ns(tr, 1) == 70
    assert devtrace.mean_busy_s(tr) == pytest.approx(55e-9)
    assert devtrace.window_s(tr) == pytest.approx(100e-9)


def test_named_ops_by_hand():
    tr = hand_trace()
    assert devtrace.op_seconds(tr, devtrace.is_codec_kernel, chip=0) == pytest.approx(10e-9)
    top = dict(devtrace.top_ops(tr, chip=0))
    assert top["fusion.1"] == pytest.approx(25e-9)


def test_idle_gaps_named_by_host_span():
    gaps = devtrace.idle_gaps(hand_trace(), chip=0)
    # idle on chip 0: [0, 10) in bench.step, [35, 50) and [60, 95) in bench.wait
    assert gaps == [["bench.wait", pytest.approx(35e-9)],
                    ["bench.wait", pytest.approx(15e-9)],
                    ["bench.step", pytest.approx(10e-9)]]



def test_recorded_slice_names_only_the_kernels():
    # on the chip an op is named by its whole HLO text, so the fusion that
    # reads the decode's output names the kernel among its operands
    tr = devtrace.Trace.from_json(json.loads(RECORDED.read_text()))
    kernels = {devtrace.op_name(n) for _c, n, _s, _d in tr.ops
               if devtrace.is_codec_kernel(n)}
    assert kernels == {"_fused_encode_percoord.18", "_fused_decode_percoord.18"}
    consumer = [n for _c, n, _s, _d in tr.ops
                if devtrace.op_name(n) == "broadcast_multiply_fusion.8"]
    assert consumer and "_fused_decode_percoord.18" in consumer[0]
    assert devtrace.op_seconds(tr, devtrace.is_codec_kernel) == pytest.approx(
        (3801650 + 3963972) * 1e-9)
    top = devtrace.top_ops(tr)
    assert top[0] == ["_fused_decode_percoord.18", pytest.approx(3963972e-9)]
