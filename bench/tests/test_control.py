"""The readings that the limits are set from, at a size a test run holds.
``bench/control.py`` makes the same readings on the chip at each cell's own
size; PERF.md gives those readings and the limits set from them.

Training cells: the sound program passes every limit and the half batch,
the fault planted in the reference put in the program's place, fails one.
The float8 control is read but not asserted on: at the cell's own size
the codec's noise hides it from every number (PERF.md).  Codec cell: the
program's own per-tensor shared randomness fails the cell's limit, which
holds at every size.
"""
import contextlib
import io
import json

import common
import control
import tiny


def readings(cell, seeds, only):
    out = io.StringIO()
    with tiny.tiny_size(), contextlib.redirect_stdout(out):
        control.main(["--workload", cell, "--seeds", seeds, "--only", only])
    return [json.loads(x) for x in out.getvalue().strip().splitlines()]


def test_train_half_batch_fails_the_limits():
    cell = "train.starcoder2-3b.tensor"
    limit = common.read_json(common.BENCH / "limits" / f"{cell}.json")
    got = readings(cell, "21,22", "program,control,faults")
    for g in got:
        assert all(g["program"][k] <= v for k, v in limit.items()), g
        assert any(g["half_batch"][k] > v for k, v in limit.items()), g
        assert set(limit) <= set(g["control"]), g


def test_codec_control_fails_the_limit():
    cell = "codec.qwen1.5-0.5b.layer.coord"
    limit = common.read_json(common.BENCH / "limits" / f"{cell}.json")
    got = readings(cell, "21,22", "program,control")
    for g in got:
        assert g["program"]["ks_scaled"] <= limit["ks_scaled"]
        assert g["control"]["ks_scaled"] > limit["ks_scaled"]
