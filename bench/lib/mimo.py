"""MIMO channel pool: i.i.d. Rayleigh 4x4 channels as 8x8 real matrices.

Each subcarrier's complex channel ``H = Re H + j Im H`` (entries CN(0, 1))
becomes its real-valued decomposition ``[[Re H, -Im H], [Im H, Re H]]``,
the form real-arithmetic MIMO detectors factor: for any complex x,
``RVD(H) @ [Re x; Im x] == [Re(Hx); Im(Hx)]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import seeds


def rvd(re, im):
    """Real-valued decomposition of complex matrices given as (re, im)."""
    top = jnp.concatenate([re, -im], axis=-1)
    bot = jnp.concatenate([im, re], axis=-1)
    return jnp.concatenate([top, bot], axis=-2)


@functools.partial(jax.jit, static_argnames=("slots", "batch", "rx", "tx"))
def _pool(key, *, slots, batch, rx, tx):
    def one(k):
        k_re, k_im = jax.random.split(k)
        scale = jnp.float32(0.5 ** 0.5)           # CN(0, 1): each part N(0, 1/2)
        re = jax.random.normal(k_re, (batch, rx, tx), jnp.float32) * scale
        im = jax.random.normal(k_im, (batch, rx, tx), jnp.float32) * scale
        return rvd(re, im).astype(jnp.float64)
    return tuple(one(jax.random.fold_in(key, i)) for i in range(slots))


def channel_pool(seed: int, *, slots: int, batch: int, rx: int, tx: int):
    """``slots`` distinct device arrays of shape (batch, 2 rx, 2 tx) float64,
    made on the device in one jitted call from ``seed``."""
    return list(_pool(seeds.jax_key(seed, 1), slots=slots, batch=batch,
                      rx=rx, tx=tx))
