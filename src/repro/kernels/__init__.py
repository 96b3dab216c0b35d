"""Pallas TPU kernels of the wire path (quantize, pack) and the SSD scan.

Each kernel has a pure-jnp oracle (ref.py) it is bit-identical to, and an
ops.py that dispatches on `impl`: 'ref' (the oracle), 'pallas' (the kernel
body in interpret mode, for CPU contract tests) or 'pallas_compiled' (the
Mosaic-compiled kernel, TPU).  Interpret mode never runs on a TPU.
"""
import jax
import jax.numpy as jnp


def check_interpret(interpret: bool) -> None:
    """Refuse interpret mode on a TPU backend, where only the compiled
    kernel may run (the interpreter would silently stand in for it)."""
    if interpret and jax.default_backend() == "tpu":
        raise ValueError("Pallas interpret mode requested on a TPU backend; "
                         "use impl='pallas_compiled' (interpret=False)")


def take_flat(x2: jax.Array, n: int) -> jax.Array:
    """First n elements of a (rows, cols) buffer in row-major order.

    Equivalent to x2.reshape(-1)[:n], but slices the row/tail parts before
    flattening: XLA:CPU miscompiles the fused reshape -> odd-length-slice
    pattern for some n under SPMD partitioning (same bug family as
    kernels/pack ref.take_levels)."""
    rows, cols = x2.shape
    full = n // cols
    tail = n - full * cols
    parts = []
    if full:
        parts.append(x2[:full].reshape(-1))
    if tail:
        parts.append(x2[full, :tail])
    if not parts:
        return jnp.zeros((0,), x2.dtype)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)
