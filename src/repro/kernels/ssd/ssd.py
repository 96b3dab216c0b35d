"""Pallas TPU kernel: intra-chunk SSD (the quadratic half of Mamba2's chunked
state-space-duality algorithm).

TPU mapping: grid over (batch*chunks, head-blocks).  Per grid cell everything
lives in VMEM:
  C, B: (Q, N)           -> one (Q, Q) MXU matmul
  la, dt: (Q, HB)        -> elementwise decay weights (VPU)
  x: (Q, HB, P)          -> HB small (Q, Q) x (Q, P) MXU matmuls
with Q = chunk length (128/256), N = state (64-128), P = head dim (64):
Q, N, P are all MXU-friendly multiples; the decay matrix never touches HBM —
that is the kernel's point (the jnp path materializes (B, NC, Q, Q, H) decay
tensors through HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import check_interpret

Array = jax.Array

NEG_INF = float("-inf")


def _kernel(x_ref, dt_ref, la_ref, b_ref, c_ref, out_ref):
    # blocks (leading grid dim of size 1 squeezed on read):
    #   x (Q, HB, P); dt, la (Q, HB); b, c (Q, N)
    f32 = jnp.float32
    x = x_ref[0].astype(f32)                         # (Q, HB, P)
    dt = dt_ref[0].astype(f32)                       # (Q, HB)
    la = la_ref[0].astype(f32)                       # (Q, HB)
    bmat = b_ref[0].astype(f32)                      # (Q, N)
    cmat = c_ref[0].astype(f32)                      # (Q, N)
    q, hb = x.shape[0], x.shape[1]
    cb = jnp.dot(cmat, bmat.T,
                 preferred_element_type=f32)         # (Q, Q) on the MXU
    tri = jnp.tril(jnp.ones((q, q), jnp.bool_))

    def head(h, acc):
        seg = la[:, None, h] - la[None, :, h]        # (Q, Q)
        decay = jnp.exp(jnp.where(tri, seg, NEG_INF))
        w = cb * decay * dt[None, :, h]              # (Q, Q)
        yh = jnp.dot(w, x[:, h, :],
                     preferred_element_type=f32)     # (Q, P) MXU
        return acc.at[:, h, :].set(yh)

    out = jax.lax.fori_loop(0, hb, head, jnp.zeros(x.shape, f32))
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("head_block", "interpret"))
def ssd_intra(x: Array, dt: Array, la: Array, b: Array, c: Array,
              *, head_block: int = 8, interpret: bool = True) -> Array:
    """Batched intra-chunk SSD.

    x: (BC, Q, H, P); dt, la: (BC, Q, H); b, c: (BC, Q, N) — BC = batch*chunks
    flattened, G=1 groups.  Returns (BC, Q, H, P) f32.
    """
    check_interpret(interpret)
    bc, q, h, p = x.shape
    n = b.shape[-1]
    hb = min(head_block, h)
    nhb = -(-h // hb)
    pad = nhb * hb - h
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad)))
        la = jnp.pad(la, ((0, 0), (0, 0), (0, pad)))
    grid = (bc, nhb)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, hb, p), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, q, hb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, q, hb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, q, n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, hb, p), lambda i, j: (i, 0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bc, q, nhb * hb, p), jnp.float32),
        interpret=interpret,
        name="ssd_intra",
    )(x, dt, la, b, c)
    return out[:, :, :h]
