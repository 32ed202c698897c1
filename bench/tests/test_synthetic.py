"""The benchmark's token generator makes the tokens of the program's."""
import jax
import numpy as np

import common
import synthetic


def test_closed_form_equals_the_scan():
    for vocab in (151936, 49152, 256):
        key = synthetic.step_key(common.seed_key(2**31 + 17), 3)
        a = synthetic.lm_batch(key, 4, 2048, vocab)
        b = synthetic.lm_batch_scan(key, 4, 2048, vocab)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rows_and_steps_differ():
    k = common.seed_key(5)
    a = np.asarray(synthetic.lm_batch(synthetic.step_key(k, 1), 16, 64, 151936))
    b = np.asarray(synthetic.lm_batch(synthetic.step_key(k, 2), 16, 64, 151936))
    assert len({tuple(r) for r in a}) == 16
    assert not np.array_equal(a, b)


def test_large_seeds():
    a = common.seed_key(2**31 + 5)
    b = common.seed_key(5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    jax.random.normal(a, (2,))
