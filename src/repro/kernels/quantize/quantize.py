"""Pallas TPU kernel: fused stochastic quantize + dequantize.

The Q-GADMM per-iteration communication hot path touches every parameter:
read theta and theta_hat_prev, compute level indices with stochastic rounding,
write the uint8 payload and, in `quantize_dequantize`, the reconstructed
theta_hat.  Unfused, XLA materializes the f32 intermediates (c, floor, p,
compare) in HBM; fused, the op is 3 reads (theta, hat, u) + 2 writes
(q, hat_new) of which q is 1 byte/elem.  `quantize` writes q alone: the
dist trainer's sender takes its hat from the receivers' decode of q
(dist.qgadmm._decode), so sender and receiver hats never rest on Mosaic and
XLA rounding the dequantize alike.

TPU mapping: pure VPU elementwise work tiled in (BLOCK_M, 128) VMEM blocks,
lane-dim 128-aligned.  Scalars (radius, levels) ride in SMEM via (1,1) blocks.
Arithmetic intensity is O(1) FLOP/byte => the win is HBM traffic, not MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import check_interpret, take_flat

Array = jax.Array

BLOCK_M = 256  # sublane-dim block; lane dim fixed at 128
LANES = 128


def _qdq_math(radius, levels, theta_ref, hat_ref, u_ref, q_ref, *newhat_ref):
    """Shared kernel body: the scalar-radius and tile-radius variants must
    stay bit-identical (the trainer's cross-impl parity contract), so the
    arithmetic lives in exactly one place.  radius is a scalar or a tile
    broadcastable against the block.  newhat_ref is absent in the q-only
    form (`quantize`)."""
    x = theta_ref[...].astype(jnp.float32)
    h = hat_ref[...].astype(jnp.float32)
    u = u_ref[...]
    safe_r = jnp.maximum(radius, 1e-30)
    step = 2.0 * safe_r / levels
    c = (x - h + radius) / step
    low = jnp.floor(c)
    p = c - low
    q = low + (u < p).astype(jnp.float32)
    q = jnp.clip(q, 0.0, levels)
    active = radius > 0
    # Mosaic has no f32 -> u8 cast: convert through int32 (exact, q is an
    # integer in [0, 255]) and narrow at the store.
    q_ref[...] = jnp.where(active, q, jnp.zeros_like(q)).astype(
        jnp.int32).astype(jnp.uint8)
    for out in newhat_ref:
        hat = h + step * q - radius
        out[...] = jnp.where(active, hat, h).astype(out.dtype)


def _kernel(r_ref, lv_ref, theta_ref, hat_ref, u_ref, *outs):
    _qdq_math(r_ref[0, 0], lv_ref[0, 0], theta_ref, hat_ref, u_ref, *outs)


def _kernel_vec_r(lv_ref, theta_ref, hat_ref, u_ref, r_ref, *outs):
    """Per-element radius variant: R rides in a VMEM tile instead of SMEM.

    Used by the dist trainer's per_tensor radius mode, where the per-tensor
    scalars are expanded (segment-scalar gather) into one radius value per
    wire-buffer position."""
    _qdq_math(r_ref[...], lv_ref[0, 0], theta_ref, hat_ref, u_ref, *outs)


def _kernel_vec_rl(theta_ref, hat_ref, u_ref, r_ref, lv_ref, *outs):
    """Per-element radius AND levels variant: both ride in VMEM tiles.

    Used by the dist trainer's layerwise mode, where each leaf owns its own
    bit width — the per-leaf (2^b - 1) scalars are expanded into one levels
    value per wire-buffer position, same segment-scalar gather as the
    per_tensor radius.  Padding positions carry levels = 1 (never 0: the
    shared math divides by levels) with R = 0 keeping them inert."""
    _qdq_math(r_ref[...], lv_ref[...], theta_ref, hat_ref, u_ref, *outs)


def _fused(theta, theta_hat_prev, u, radius, levels, interpret, with_hat):
    """The pallas_call behind `quantize_dequantize` (with_hat) and
    `quantize` (q only): picks the variant from the radius/levels ranks."""
    check_interpret(interpret)
    orig_shape = theta.shape
    n = theta.size
    cols = LANES
    rows = -(-n // cols)
    pad = rows * cols - n

    def to2d(x, fill):
        flat = x.reshape(-1)
        if pad:
            flat = jnp.concatenate([flat, jnp.full((pad,), fill, flat.dtype)])
        return flat.reshape(rows, cols)

    theta2 = to2d(theta, 0)
    hat2 = to2d(theta_hat_prev, 0)
    u2 = to2d(u.astype(jnp.float32), 1.0)  # u=1 never rounds up on padding

    block_m = min(BLOCK_M, rows)
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    tile = pl.BlockSpec((block_m, cols), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, cols), jnp.uint8)]
    if with_hat:
        out_shape.append(
            jax.ShapeDtypeStruct((rows, cols), theta_hat_prev.dtype))
    if levels.ndim > 0:
        # layerwise per-element levels: fill padding with 1 (the math
        # divides by levels), R = 0 keeps those positions inert
        r_full = (jnp.broadcast_to(radius, theta.shape) if radius.ndim == 0
                  else radius)
        kernel, specs = _kernel_vec_rl, [tile] * 5
        args = (theta2, hat2, u2, to2d(r_full.astype(jnp.float32), 0.0),
                to2d(levels.astype(jnp.float32), 1.0))
    elif radius.ndim == 0:
        kernel, specs = _kernel, [scalar, scalar, tile, tile, tile]
        args = (radius.astype(jnp.float32).reshape(1, 1),
                levels.astype(jnp.float32).reshape(1, 1), theta2, hat2, u2)
    else:
        # R == 0 on padding: inactive lanes write q = 0, discarded below.
        kernel, specs = _kernel_vec_r, [scalar] + [tile] * 4
        args = (levels.astype(jnp.float32).reshape(1, 1), theta2, hat2, u2,
                to2d(radius.astype(jnp.float32), 0.0))
    outs = pl.pallas_call(
        kernel,
        grid=(-(-rows // block_m),),
        in_specs=specs,
        out_specs=[tile] * len(out_shape),
        out_shape=out_shape,
        interpret=interpret,
        name="quantize_dequantize" if with_hat else "quantize",
    )(*args)
    return tuple(take_flat(o, n).reshape(orig_shape) for o in outs)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_dequantize(
    theta: Array,
    theta_hat_prev: Array,
    u: Array,
    radius: Array,
    levels: Array,
    *,
    interpret: bool = True,
) -> tuple[Array, Array]:
    """Fused stochastic quantize-dequantize over an arbitrary-shape tensor.

    See ref.quantize_dequantize_ref for semantics.  `radius` is a scalar
    (one R for the whole tensor, SMEM path) or an array of theta's shape
    (per-element R, VMEM tile path — the dist trainer's per_tensor mode).
    `levels` is a scalar (one bit width, SMEM) or an array of theta's shape
    (per-element levels, VMEM tile — the layerwise per-leaf bit widths); the
    per-element-levels path always runs the vec-R kernel (a scalar radius is
    broadcast).  interpret=True executes the kernel body in Python (CPU
    contract tests; refused on a TPU backend); interpret=False compiles it
    with Mosaic for the TPU.
    """
    return _fused(theta, theta_hat_prev, u, radius, levels, interpret,
                  with_hat=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize(
    theta: Array,
    theta_hat_prev: Array,
    u: Array,
    radius: Array,
    levels: Array,
    *,
    interpret: bool = True,
) -> Array:
    """The levels q of `quantize_dequantize` alone (same variants, same
    arithmetic): 3 reads and a 1-byte write per element.  The dist
    trainer's sender uses it and decodes its new hat from q with the
    receivers' own function, so both ends of an edge agree by
    construction."""
    (q,) = _fused(theta, theta_hat_prev, u, radius, levels, interpret,
                  with_hat=False)
    return q
