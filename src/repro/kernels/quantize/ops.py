"""Public jit'd wrapper for the fused stochastic quantize-dequantize kernel.

Dispatches to the Pallas kernel (interpret mode on CPU, compiled on TPU) or to
the pure-jnp reference, selected by `impl`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantizer import levels_of

from . import quantize as _kernel
from . import ref as _ref

Array = jax.Array


def quantize_dequantize(
    theta: Array,
    theta_hat_prev: Array,
    key: Array,
    radius: Array,
    bits: Array | int,
    *,
    impl: str = "pallas",
) -> tuple[Array, Array]:
    """Stochastically quantize (theta - theta_hat_prev); return (q uint8, new hat).

    impl: 'pallas' (interpret on CPU), 'pallas_compiled' (TPU), or 'ref'.
    radius: scalar, or theta-shaped for per-element quantization ranges.
    """
    u = jax.random.uniform(key, theta.shape, jnp.float32)
    levels = levels_of(bits)
    radius = jnp.asarray(radius, jnp.float32)
    if impl == "ref":
        return _ref.quantize_dequantize_ref(theta, theta_hat_prev, u, radius, levels)
    interpret = impl != "pallas_compiled"
    return _kernel.quantize_dequantize(
        theta, theta_hat_prev, u, radius, levels, interpret=interpret
    )
