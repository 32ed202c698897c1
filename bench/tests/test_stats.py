"""The device KS statistic equals the host one copied from the program's
test helpers."""
import jax
import jax.numpy as jnp
import pytest

import stats


@pytest.mark.parametrize("scale", [1.0, 1.05])
def test_device_ks_equals_host_ks(scale):
    sigma = 0.05
    err = scale * sigma * jax.random.normal(jax.random.PRNGKey(3), (20000,))
    host = stats.ks_statistic(err, lambda s: stats.norm_cdf(s, sigma))
    dev = stats.ks_normal_device(jnp.asarray(err, jnp.float32), sigma)
    assert dev == pytest.approx(host, abs=2e-6)
    assert (host < stats.ks_threshold(err.size)) == (scale == 1.0)
