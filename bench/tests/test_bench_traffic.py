"""The traffic generators: deterministic in the seed, stated shapes."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import mimo, seeds

BIG = 2**33 + 7          # seeds run past 32 signed bits


def test_seed_words():
    assert seeds.words(BIG, 3) == [3, 7, 2]
    with pytest.raises(ValueError):
        seeds.words(-1)


def test_rvd_equals_complex_product():
    rng = np.random.default_rng(0)
    H = rng.standard_normal((5, 4, 4)) + 1j * rng.standard_normal((5, 4, 4))
    x = rng.standard_normal((5, 4)) + 1j * rng.standard_normal((5, 4))
    G = np.asarray(mimo.rvd(jnp.asarray(H.real), jnp.asarray(H.imag)))
    y = np.einsum("bij,bj->bi", G, np.concatenate([x.real, x.imag], -1))
    hx = np.einsum("bij,bj->bi", H, x)
    np.testing.assert_allclose(y, np.concatenate([hx.real, hx.imag], -1),
                               rtol=1e-12, atol=1e-12)


def test_channel_pool_deterministic():
    a = mimo.channel_pool(BIG, slots=3, batch=5, rx=4, tx=4)
    b = mimo.channel_pool(BIG, slots=3, batch=5, rx=4, tx=4)
    c = mimo.channel_pool(BIG + 1, slots=3, batch=5, rx=4, tx=4)
    assert len(a) == 3
    for x, y in zip(a, b):
        assert x.shape == (5, 8, 8) and x.dtype == jnp.float64
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(a[1]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    # the real-valued structure [[Re, -Im], [Im, Re]]
    g = np.asarray(a[0])
    np.testing.assert_array_equal(g[:, :4, :4], g[:, 4:, 4:])
    np.testing.assert_array_equal(g[:, :4, 4:], -g[:, 4:, :4])

