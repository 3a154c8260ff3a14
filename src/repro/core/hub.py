"""HUB (Half-Unit-Biased) numerics as a standalone primitive layer.

Beyond the paper's converter-internal use, HUB rounding is exposed here as a
cheap *unbiased-bound* round-to-nearest cast for float tensors: truncate the
mantissa to (m) bits — the implicit half-ULP then makes the representable
value the round-to-nearest of every real in the bin.  Worst-case error equals
RNE's; no sticky/round-up logic is needed, which is why the paper's HUB
datapath is smaller and faster.

`hub_quantize(x, man_bits)` returns the float value *represented by* the HUB
word (i.e. truncated mantissa + half ULP), so downstream float math sees
exactly what a HUB unit would compute.
"""
from __future__ import annotations

import jax.numpy as jnp

from .formats import frexp, ldexp

__all__ = ["hub_quantize", "hub_error_bound"]


def hub_quantize(x, man_bits: int):
    """Round float array to an m-bit-mantissa HUB value (value-level emulation).

    Works for any float dtype; computed in float64 for exactness.
    """
    xd = jnp.asarray(x, jnp.float64)
    sign = jnp.sign(xd)
    ax = jnp.abs(xd)
    is_zero = ax == 0.0
    f, e = frexp(jnp.where(is_zero, 1.0, ax))  # f in [0.5, 1)
    scale = jnp.float64(1 << (man_bits + 1))
    # truncate to man_bits fractional bits of the [1,2) significand, + ILSB
    sig = (jnp.floor(f * scale) + 0.5) / scale     # in [0.5, 1)
    out = sign * ldexp(sig, e)
    out = jnp.where(is_zero, 0.0, out)
    return out.astype(jnp.result_type(x))


def hub_error_bound(man_bits: int) -> float:
    """Worst-case relative rounding error (same bound as RNE): 2^-(m+1)."""
    return 2.0 ** -(man_bits + 1)
