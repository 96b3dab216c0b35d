"""Pallas TPU kernel: int4 nibble pack / unpack.

For b <= 4 quantizer bits the wire payload halves again by packing two levels
per byte before the collective-permute.  Elementwise VPU work on lane-dense
uint8 blocks: the level stream is viewed as (2 * rows, 128), whose rows 2r
and 2r + 1 are the low and high nibbles of packed row r.  Wire format
(strided pairing, padded) is defined in ref.py; kernel and oracle produce
bit-identical buffers.

Mosaic has no shifts on 8-bit vectors, so the nibble arithmetic runs in
int32 lanes and narrows to uint8 at the store.  The (2 * rows, 128) view
(rather than (rows, 256) or (rows, 2, 128)) keeps the reshapes around the
kernel cheap for XLA:TPU: reshaping a (1, n) wire row to (rows, 256)
uint8 takes its compiler tens of seconds at whisper-tiny's width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import check_interpret, take_flat

from .ref import LANES, _pad_rows

Array = jax.Array

BLOCK_M = 256


def _pack_kernel(q_ref, out_ref):
    # (2 * bm, 128) -> (bm, 2, 128): [:, 0] low, [:, 1] high nibbles
    q = q_ref[...].astype(jnp.int32).reshape(out_ref.shape[0], 2, LANES)
    out_ref[...] = (q[:, 0, :] | (q[:, 1, :] << 4)).astype(jnp.uint8)


def _unpack_kernel(p_ref, out_ref):
    p = p_ref[...].astype(jnp.int32)  # (bm, 128)
    lo_hi = jnp.stack([p & 0xF, p >> 4], axis=1)  # (bm, 2, 128)
    out_ref[...] = lo_hi.reshape(out_ref.shape).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack4(q: Array, *, interpret: bool = True) -> Array:
    """Pack flat uint8 levels (<16) into the wire format (128*ceil(n/256) bytes)."""
    check_interpret(interpret)
    flat = q.reshape(-1)
    rows = _pad_rows(flat.size)
    pad = rows * 2 * LANES - flat.size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.uint8)])
    q2 = flat.reshape(2 * rows, LANES)
    block_m = min(BLOCK_M, rows)
    grid = (-(-rows // block_m),)
    out = pl.pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((2 * block_m, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_m, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.uint8),
        interpret=interpret,
        name="pack4",
    )(q2)
    return out.reshape(-1)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def unpack4(packed: Array, n: int, *, interpret: bool = True) -> Array:
    """Unpack the wire format back to the first n uint8 levels."""
    check_interpret(interpret)
    rows = _pad_rows(n)
    p2 = packed.reshape(rows, LANES)
    block_m = min(BLOCK_M, rows)
    grid = (-(-rows // block_m),)
    out = pl.pallas_call(
        _unpack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((2 * block_m, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((2 * rows, LANES), jnp.uint8),
        interpret=interpret,
        name="unpack4",
    )(p2)
    # (2 * rows, 128) is already in wire order: lo row, then hi row
    return take_flat(out, n)
