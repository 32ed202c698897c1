"""A run whose timed path is broken underneath must come out not correct.

Each test drives a whole run of a cell at the tiny size on the CPU
(``tiny.run_cell``: the harness's look for a chip is skipped), with one
fault planted in the program, under the cell's own limits from
``bench/limits``.  The same run without the fault comes out correct.
"""
import functools

import jax.numpy as jnp
import pytest

import common
import tiny

from repro.dist import compress
from repro.train import steps

TRAIN = "train.starcoder2-3b.tensor"
CODEC = "codec.qwen1.5-0.5b.layer.coord"


def limits(cell):
    return common.read_json(common.BENCH / "limits" / f"{cell}.json")


def correct(cell, monkeypatch=None, patches=None):
    for (mod, attr), fn in (patches or {}).items():
        monkeypatch.setattr(mod, attr, fn)
    res, rc = tiny.run_cell(cell, limits(cell))
    assert rc == 0 and res is not None
    return res["correct"], res["checks"]


def _wrap_step(fault):
    build = steps.build_train_step

    def patched(cfg, tc, mesh):
        step = build(cfg, tc, mesh)

        def broken(state, batch, seed):
            return fault(step, state, batch, seed)

        return broken

    return patched


def _unchanged(step, state, batch, seed):
    return state, step(state, batch, seed)[1]


def _half_batch(step, state, batch, seed):
    half = batch["tokens"].shape[0] // 2
    return step(state, {"tokens": batch["tokens"][:half]}, seed)


def _token_altered(step, state, batch, seed):
    # the last row's tokens shifted by one where the batch is produced
    t = batch["tokens"]
    return step(state, {"tokens": t.at[-1].set((t[-1] + 1) % 256)}, seed)


def test_sound_train_run_is_correct():
    ok, checks = correct(TRAIN)
    assert ok, checks


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_train_fault_is_caught(fault, monkeypatch):
    ok, checks = correct(TRAIN, monkeypatch,
                         {(steps, "build_train_step"): _wrap_step(fault)})
    assert not ok, checks


def test_sound_codec_run_is_correct():
    ok, checks = correct(CODEC)
    assert ok, checks


def test_codec_answer_altered_is_caught(monkeypatch):
    decode = compress.decode_leaf_sum

    @functools.wraps(decode)
    def shifted(m_sum, comp, n, r_msgs, step, offset, s_sum, geom, shape):
        y = decode(m_sum, comp, n, r_msgs, step, offset, s_sum, geom, shape)
        return y + 0.5 * comp.sigma if y.size > 4096 else y

    ok, checks = correct(CODEC, monkeypatch,
                         {(compress, "decode_leaf_sum"): shifted})
    assert not ok, checks


def test_codec_half_the_leaves_left_out_is_caught(monkeypatch):
    tree = compress.compress_tree

    def half(grads, comp, key, axis=None, n_clients=1):
        out = tree(grads, comp, key, axis, n_clients)
        names = sorted(grads)
        for k in names[: len(names) // 2]:
            out[k] = jnp.clip(grads[k], -comp.clip, comp.clip)
        return out

    ok, checks = correct(CODEC, monkeypatch,
                         {(compress, "compress_tree"): half})
    assert not ok, checks


def test_metric_with_nothing_to_read_fails_the_run():
    # the CPU's trace holds no TPU operation, so every per-layer reader of
    # the cell finds nothing: the run exits non-zero and prints no result
    res, rc = tiny.run_cell(CODEC, limits(CODEC), trace=1)
    assert rc != 0 and res is None
