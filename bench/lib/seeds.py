"""Seeds: the driver's ``--seed`` is any whole number up to a little over
2**31, so every generator takes it as two 32-bit words."""
from __future__ import annotations


def words(seed: int, stream: int = 0) -> list[int]:
    """``[stream, low 32 bits, high bits]`` for `numpy.random.default_rng`."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return [int(stream), seed & 0xFFFFFFFF, seed >> 32]


def jax_key(seed: int, stream: int = 0):
    """A JAX PRNG key from the whole seed (both words folded in)."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)
