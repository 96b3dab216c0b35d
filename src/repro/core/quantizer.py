"""Stochastic quantizer of Q-GADMM (paper eqs. 6-13).

Worker n at iteration k quantizes the *difference* between its current model
theta_n^k and its previously-quantized model theta_hat_n^{k-1}:

    R      = ||theta - theta_hat_prev||_inf                 (quantization radius)
    Delta  = 2 R / (2^b - 1)                                (step size)
    c_i    = (theta_i - theta_hat_prev_i + R) / Delta       (non-negative coords)
    q_i    = ceil(c_i)  w.p.  c_i - floor(c_i)              (stochastic rounding,
             floor(c_i) otherwise                            eq. 7 + eq. 10)
    theta_hat = theta_hat_prev + Delta * q - R * 1          (reconstruction, eq. 13)

The rounding probability choice makes E[theta_hat] = theta (unbiased, eq. 8)
with per-coordinate variance <= Delta^2 / 4.

The payload actually transmitted is (q:int levels, R:f32, b:int) ->
b*d + 32 + 32 bits instead of 32*d bits for a full-precision vector; see
header_bits / payload_bits (the same accounting rule backs
gadmm.bits_per_round and the distributed trainer's metrics).

Everything here is pure JAX and jit/vmap/pjit friendly.  A fused Pallas TPU
kernel for the same computation lives in repro/kernels/quantize (ops.q_dequantize
dispatches to it when enabled).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class QuantizerConfig:
    """Static configuration of the stochastic quantizer.

    bits:       quantizer resolution b (levels = 2^b - 1 intervals).  The paper
                uses b=2 for linear regression and b=8 for the DNN task.
    adapt_bits: if True, apply the bit-growth rule (eq. 11) that keeps
                Delta_n^k non-increasing.  The paper observes R_n^k decreases in
                practice so fixed bits suffice; both modes are supported.
    max_bits:   cap for adaptive bits (payload dtype is int8 / packed int4).

    (Tighter-than-global ranges are provided by the distributed trainer's
    radius_mode='per_tensor'; see repro.dist.qgadmm.)
    """

    bits: int = 2
    adapt_bits: bool = False
    max_bits: int = 8

    def __post_init__(self):
        assert 1 <= self.bits <= self.max_bits <= 8


@dataclasses.dataclass(frozen=True)
class LayerwiseConfig:
    """Per-leaf (L-FGADMM, arXiv:1911.03654) quantization knobs.

    Attached as DistConfig.layerwise; the distributed trainer resolves every
    field against the model's flat leaf list (resolve()) and gives each
    pytree leaf its own bit width, exchange period and censor threshold.
    An unsent leaf rides the wire with radius 0 — the codec's R == 0 guard
    makes it a bitwise no-op on both endpoints, so receivers hold the leaf's
    last hat and the sender==receiver sync invariant survives.

    bits:       per-leaf base bit widths — an int (all leaves), a tuple of
                length L (leaf order = jax.tree.leaves), or None (fall back
                to QuantizerConfig.bits).
    periods:    per-leaf exchange periods — int or length-L tuple; leaf l is
                transmitted only on rounds where step % periods[l] == 0.
    large_leaf_period / large_leaf_frac: size-based period rule for CLI use
                (tuples don't fit on a command line): any leaf holding at
                least large_leaf_frac of the total parameters gets period
                large_leaf_period.  An explicit `periods` tuple wins.
    taus:       optional per-leaf censor thresholds (L2, like
                censor.CensorConfig.tau but per leaf) — float or length-L
                tuple; leaf l is transmitted only when its committed
                quantized delta moved more than taus[l] * tau_xi**step.
    tau_xi:     decay of the per-leaf thresholds (CQ-GGADMM's xi).
    adapt_bits: apply the eq. 11 bit-growth rule per leaf (each leaf tracks
                its own radius ratio; first transmission falls back to the
                leaf's base bits).
    budget_bits: total payload-bit budget per worker per round for the
                adaptive bit-budget controller (allocate_bits): each round
                the budget is reallocated toward the leaves whose quantized
                deltas moved most.  When set it supersedes the static /
                eq. 11 widths — the controller is itself adaptive.  None
                disables the controller.
    min_bits / max_bits: controller range (and eq. 11 cap).
    """

    bits: Any = None
    periods: Any = 1
    large_leaf_period: int = 1
    large_leaf_frac: float = 0.5
    taus: Any = None
    tau_xi: float = 1.0
    adapt_bits: bool = False
    budget_bits: int | None = None
    min_bits: int = 1
    max_bits: int = 8

    def __post_init__(self):
        assert 1 <= self.min_bits <= self.max_bits <= 8
        assert self.large_leaf_period >= 1
        assert 0.0 < self.large_leaf_frac <= 1.0
        assert 0.0 < self.tau_xi <= 1.0
        assert self.budget_bits is None or self.budget_bits > 0
        for name in ("bits", "periods"):
            v = getattr(self, name)
            if isinstance(v, int):
                assert v >= 1, (name, v)
            elif v is not None:
                assert all(int(b) >= 1 for b in v), (name, v)
        if isinstance(self.bits, int):
            assert self.bits <= self.max_bits

    def _expand(self, value, sizes, default):
        n = len(sizes)
        if value is None:
            value = default
        if isinstance(value, (int, float)):
            return [value] * n
        assert len(value) == n, (
            f"layerwise field of length {len(value)} vs {n} leaves")
        return list(value)

    def resolve(self, sizes, base_bits: int):
        """Per-leaf tables for a model with flat leaf sizes `sizes`.

        Returns (bits, periods, taus): int lists of length L (taus None when
        no per-leaf censoring is configured).  Pure-python/static — the
        trainer bakes the result into the compiled step.
        """
        bits = [int(b) for b in self._expand(self.bits, sizes, base_bits)]
        assert all(1 <= b <= self.max_bits for b in bits), bits
        periods = [int(p) for p in self._expand(self.periods, sizes, 1)]
        if self.large_leaf_period > 1 and not isinstance(
                self.periods, (tuple, list)):
            total = max(sum(sizes), 1)
            periods = [self.large_leaf_period
                       if s >= self.large_leaf_frac * total else p
                       for p, s in zip(periods, sizes)]
        taus = (None if self.taus is None
                else [float(t) for t in self._expand(self.taus, sizes, 0.0)])
        return bits, periods, taus


def allocate_bits(scores: Array, sizes: Array, budget_bits: int,
                  min_bits: int, max_bits: int) -> Array:
    """Adaptive bit-budget controller: spend `budget_bits` of payload on the
    leaves whose quantized deltas moved most.

    scores: (..., L) per-leaf residual magnitudes (any nonnegative ranking
      score; the trainer uses the per-leaf L2 of theta - theta_hat, the same
      quantity the censoring rule thresholds).
    sizes:  (L,) static per-leaf element counts.
    Returns (..., L) int32 bit widths with min_bits <= b_l <= max_bits and
      sum_l b_l * sizes_l <= max(budget_bits, min_bits * sum(sizes)) — every
      leaf is floored at min_bits (the floor is spent even when the budget
      cannot cover it), and the remaining budget upgrades leaves in strict
      score order: a leaf is upgraded as far as the budget left over after
      fully upgrading every better-ranked leaf allows.
    """
    sizes = jnp.asarray(sizes, jnp.float32)
    scores = jnp.asarray(scores, jnp.float32)
    span = float(max_bits - min_bits)
    avail = jnp.maximum(
        float(budget_bits) - float(min_bits) * jnp.sum(sizes), 0.0)
    order = jnp.argsort(-scores, axis=-1)                       # best first
    cost = jnp.broadcast_to(span * sizes, scores.shape)
    cost_sorted = jnp.take_along_axis(cost, order, axis=-1)
    spent_before = jnp.cumsum(cost_sorted, axis=-1) - cost_sorted
    room = jnp.maximum(avail - spent_before, 0.0)
    add_sorted = jnp.clip(
        jnp.floor(room / jnp.maximum(
            jnp.take_along_axis(
                jnp.broadcast_to(sizes, scores.shape), order, axis=-1),
            1.0)),
        0.0, span)
    inv = jnp.argsort(order, axis=-1)
    add = jnp.take_along_axis(add_sorted, inv, axis=-1)
    return (min_bits + add).astype(jnp.int32)


@dataclasses.dataclass
class QuantState:
    """Carried across iterations for one worker's tensor (pytree)."""

    theta_hat: Any  # previously quantized model \hat{theta}^{k-1}
    radius: Array   # R^{k-1}   (scalar, or (num_blocks,) in block mode)
    bits: Array     # b^{k-1}   (scalar int32)


def init_state(theta: Any, cfg: QuantizerConfig) -> QuantState:
    """Quantizer state at k=0: theta_hat = 0 (paper initializes theta^0 = 0)."""
    zeros = jax.tree.map(jnp.zeros_like, theta)
    radius = jnp.zeros((), jnp.float32)
    return QuantState(theta_hat=zeros, radius=radius, bits=jnp.asarray(cfg.bits, jnp.int32))


def levels_of(bits) -> Array:
    """2^b - 1 as f32, exactly, from an integer bit width (traced or not).

    Built by an integer shift, so the level grid never rests on how a
    backend evaluates a traced f32 `2.0 ** b`; the sender, the receivers
    and the reference all take their levels from here."""
    b = jnp.asarray(bits).astype(jnp.int32)
    return (jnp.left_shift(jnp.ones_like(b), b) - 1).astype(jnp.float32)


def _next_bits(cfg: QuantizerConfig, bits_prev: Array, r_new: Array,
               r_prev: Array, base_bits: Array | None = None) -> Array:
    """Bit-growth rule (eq. 11): smallest b s.t. Delta^k <= Delta^{k-1}.

    Elementwise over broadcast-compatible (bits_prev, r_new, r_prev) — the
    layerwise trainer passes (W, L) arrays to run the rule per leaf.
    `base_bits` overrides cfg.bits as the r_prev == 0 fallback (per-leaf
    configured widths); None keeps the global configured bits.
    """
    base = (jnp.asarray(cfg.bits, jnp.int32) if base_bits is None
            else jnp.asarray(base_bits, jnp.int32))
    if not cfg.adapt_bits:
        return jnp.broadcast_to(base, jnp.broadcast_shapes(
            base.shape, jnp.shape(r_new)))
    levels_prev = levels_of(bits_prev)
    ratio = jnp.where(r_prev > 0, r_new / jnp.maximum(r_prev, 1e-30), 0.0)
    needed = jnp.ceil(jnp.log2(1.0 + levels_prev * ratio))
    b = jnp.clip(needed.astype(jnp.int32), 1, cfg.max_bits)
    # first iteration (r_prev == 0): fall back to configured bits
    return jnp.where(r_prev > 0, b, base)


def quantize_tensor(
    theta: Array,
    theta_hat_prev: Array,
    key: Array,
    *,
    radius: Array,
    bits: Array,
) -> tuple[Array, Array]:
    """Quantize one tensor given a (scalar) radius and bit width.

    Returns (q_levels uint8, theta_hat_new).  Levels fit in [0, 2^b - 1] <= 255.
    theta_hat_new is returned in theta_hat_prev's dtype — the same rule
    dequantize_tensor applies on the receiver — so sender and receiver stay
    bit-identical even for mixed-precision pytrees (theta in bf16, hat state
    in f32).  The fused Pallas kernel (repro.kernels.quantize) follows the
    same contract.
    """
    delta_theta = theta.astype(jnp.float32) - theta_hat_prev.astype(jnp.float32)
    levels = levels_of(bits)
    # Guard R == 0 (already converged / first step with theta == theta_hat):
    # then q is all-zero and theta_hat is unchanged.
    safe_r = jnp.maximum(radius, 1e-30)
    step = 2.0 * safe_r / levels
    c = (delta_theta + radius) / step
    low = jnp.floor(c)
    p = c - low  # eq. (10)
    u = jax.random.uniform(key, theta.shape, jnp.float32)
    q = low + (u < p).astype(jnp.float32)
    q = jnp.clip(q, 0.0, levels)
    q = jnp.where(radius > 0, q, jnp.zeros_like(q))
    theta_hat = theta_hat_prev.astype(jnp.float32) + step * q - radius
    theta_hat = jnp.where(radius > 0, theta_hat, theta_hat_prev.astype(jnp.float32))
    return q.astype(jnp.uint8), theta_hat.astype(theta_hat_prev.dtype)


def dequantize_tensor(
    q: Array,
    theta_hat_prev: Array,
    *,
    radius: Array,
    bits: Array,
) -> Array:
    """Reconstruction (eq. 13) on the receiver side."""
    levels = levels_of(bits)
    safe_r = jnp.maximum(radius, 1e-30)
    step = 2.0 * safe_r / levels
    out = theta_hat_prev.astype(jnp.float32) + step * q.astype(jnp.float32) - radius
    return jnp.where(radius > 0, out, theta_hat_prev.astype(jnp.float32)).astype(
        theta_hat_prev.dtype
    )


def global_radius(theta: Any, theta_hat_prev: Any) -> Array:
    """R^k = || theta - theta_hat_prev ||_inf over the whole pytree."""
    leaves = jax.tree.leaves(
        jax.tree.map(
            lambda a, b: jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
            if a.size
            else jnp.zeros((), jnp.float32),
            theta,
            theta_hat_prev,
        )
    )
    return jnp.max(jnp.stack(leaves)) if leaves else jnp.zeros((), jnp.float32)


def quantize(
    theta: Any,
    state: QuantState,
    key: Array,
    cfg: QuantizerConfig,
) -> tuple[dict[str, Any], QuantState]:
    """Quantize a pytree of tensors with one shared radius (paper-faithful).

    Returns (payload, new_state).  payload = {'q': pytree uint8, 'radius': f32,
    'bits': i32}; its wire size is payload_bits(cfg, d) bits.
    The *sender-side* new_state.theta_hat equals the receiver's reconstruction,
    keeping both sides exactly in sync (key requirement of the algorithm).
    """
    r_new = global_radius(theta, state.theta_hat)
    bits = _next_bits(cfg, state.bits, r_new, state.radius)
    leaves, treedef = jax.tree.flatten(theta)
    hat_leaves = treedef.flatten_up_to(state.theta_hat)
    keys = jax.random.split(key, max(len(leaves), 1))
    qs, hats = [], []
    for x, h, k in zip(leaves, hat_leaves, keys):
        q, hat = quantize_tensor(x, h, k, radius=r_new, bits=bits)
        qs.append(q)
        hats.append(hat)
    payload = {
        "q": jax.tree.unflatten(treedef, qs),
        "radius": r_new,
        "bits": bits,
    }
    new_state = QuantState(
        theta_hat=jax.tree.unflatten(treedef, hats), radius=r_new, bits=bits
    )
    return payload, new_state


def dequantize(payload: dict[str, Any], theta_hat_prev: Any) -> Any:
    """Receiver-side reconstruction of the sender's theta_hat^k."""
    return jax.tree.map(
        lambda q, h: dequantize_tensor(
            q, h, radius=payload["radius"], bits=payload["bits"]
        ),
        payload["q"],
        theta_hat_prev,
    )


def header_bits(adapt_bits: bool = True, num_radii: int = 1) -> int:
    """Per-transmission header: one f32 radius per radius scalar (1 in
    global mode, one per tensor in the dist trainer's per_tensor mode)
    plus the i32 bit width.

    The payload dict always carries `bits` — the protocol transmits it
    every round whether or not the bit-growth rule is active — so it is
    always billed.  (Core used to elide those 32 bits when adapt_bits was
    off, diverging from dist.qgadmm.wire_bits_per_round by one word per
    transmission; `adapt_bits` is kept for call-site compatibility but no
    longer changes the result.)

    Single source of truth for payload accounting — payload_bits,
    gadmm.bits_per_round, the dist trainer's metrics, and the sim's
    per-message billing all use it.
    """
    del adapt_bits
    return 32 * int(num_radii) + 32


def payload_bits(cfg_or_bits, num_params: int, *, adapt_bits: bool = False,
                 num_radii: int = 1) -> int:
    """Wire size in bits of one transmission: b*d + header."""
    if isinstance(cfg_or_bits, QuantizerConfig):
        b = cfg_or_bits.bits
        adapt_bits = cfg_or_bits.adapt_bits
    else:
        b = int(cfg_or_bits)
    return b * num_params + header_bits(adapt_bits, num_radii)
