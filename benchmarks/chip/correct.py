"""How `correct` is decided: the first rounds of the timed path against the
plain reference (`reference/`), number by number, each against its limit.

Set-up drives the compiled step (the window's own call and feed) through
its first CHECK_ROUNDS rounds and takes readings on the way; once the
window has closed and the program's state is freed, the reference replays
those rounds from the same seed and batches and takes the same readings.
The numbers compared:

  loss_gap     max over the rounds of |loss - loss_ref| / loss_ref.
  grad_gap     the first gradient as Adam got it (opt_mu / (1 - b1) after
               round 1): the worst (worker, leaf) gap between the program's
               norm and the reference's, over the larger of that leaf's
               reference norm and the worker's median leaf norm.
  change_gap   the same for ||theta - theta_0|| after the last round; leaves
               whose reference gradient is under 1e-3 of the median leaf's
               are left out (they move by round-off alone).
  dual_gap     the same for the edge duals after the last round (each edge
               once, from its head's mirror), per leaf.
  level_gap    quantized wire only: over each worker's own hat and each
               receiver's copy of its sender's hat, the largest share of
               sampled positions whose value lies more than half a
               quantization step from the reference's hat of that sender.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CHECK_ROUNDS = 3
ADAM_B1 = 0.9
SAMPLES = 1 << 20


def sample_positions(sizes: list[int], seed: int) -> list[np.ndarray]:
    """SAMPLES flat wire positions drawn from the seed, split per leaf."""
    d = sum(sizes)
    rng = np.random.default_rng([int(seed) % (1 << 64), 0x5A3])
    pos = np.sort(rng.choice(d, size=min(SAMPLES, d), replace=False))
    out, off = [], 0
    for n in sizes:
        sel = pos[(pos >= off) & (pos < off + n)] - off
        out.append(sel.astype(np.int32))
        off += n
    return out


def _row_norms(tree):
    """(L,) f32 norms of one row's leaves."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree.leaves(tree)])


def _row_change(tree, base):
    return _row_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        tree, base))


def _row_samples(tree, idx):
    return jnp.concatenate([a.reshape(-1)[i].astype(jnp.float32)
                            for a, i in zip(jax.tree.leaves(tree), idx)])


_norms = {"stacked": jax.jit(jax.vmap(_row_norms)), "row": jax.jit(_row_norms)}
_samples = {"stacked": jax.jit(jax.vmap(_row_samples, in_axes=(0, None))),
            "row": jax.jit(_row_samples)}


def _change(init):
    """Per-row change norms against the initial weights, which `init` (seed
    key -> weights) makes inside the same program: they live there as its
    temporaries and are never held beside the state."""
    return {"stacked": jax.jit(lambda rows, k: jax.vmap(
                _row_change, in_axes=(0, None))(rows, init(k))),
            "row": jax.jit(lambda row, k: _row_change(row, init(k)))}


def _rows(fn, rows, *args):
    """Apply a per-row reading to a stacked tree (the program's state) or
    to a list of row trees (the reference's), as a numpy (R, ...) array."""
    if isinstance(rows, list):
        return np.stack([np.asarray(fn["row"](r, *args)) for r in rows])
    return np.asarray(fn["stacked"](rows, *args))


def grad_reading(mu) -> np.ndarray:
    """(W, L) first-gradient norms from Adam's first moment after round 1."""
    return _rows(_norms, mu) / (1 - ADAM_B1)


def late_reading(views: dict, init, key, idx) -> dict:
    """Readings after the last checked round: change norms (W, L) from the
    initial weights init(key), dual norms (L,) over the head mirrors, hat
    samples of the own hats (W, K) and of the receivers' copies (2E, K)
    with each copy's sender."""
    head_rows = np.flatnonzero(np.asarray(views["sign_dst"]) > 0)
    change = _rows(_change(init), views["theta"], key)
    out = {"change": change, "own": _rows(_samples, views["theta_hat"], idx),
           "dual": np.zeros(change.shape[1]), "copies": np.zeros((0, 0)),
           "src": np.asarray(views["src"])}
    if len(head_rows):       # a worker with no neighbours has no edge state
        lam = _rows(_norms, views["lam_edge"])
        out["dual"] = np.sqrt(np.sum(lam[head_rows] ** 2, axis=0))
        out["copies"] = _rows(_samples, views["hat_edge"], idx)
    return out


def _gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """Worst (row, leaf) |prog - ref| over max(ref, the row's median)."""
    ref = np.asarray(ref, np.float64)
    prog = np.asarray(prog, np.float64)
    if ref.ndim == 1:
        ref, prog = ref[None], prog[None]
        keep = None if keep is None else keep[None]
    med = np.median(ref, axis=1, keepdims=True)
    gap = np.abs(prog - ref) / np.maximum(np.maximum(ref, med), 1e-30)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    return float(np.max(gap))


def numbers(prog: dict, ref: dict, quantized: bool, levels: float) -> dict:
    """The compared numbers from the program's and the reference's readings
    (each: 'losses', 'grad', and the late_reading keys; ref also 'radius')."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
           "grad_gap": _gap(prog["grad"], ref["grad"])}
    g = np.asarray(ref["grad"])
    moves = g >= 1e-3 * np.median(g, axis=1, keepdims=True)
    out["change_gap"] = _gap(prog["change"], ref["change"], keep=moves)
    out["dual_gap"] = _gap(prog["dual"], ref["dual"])
    if quantized:
        half_step = np.asarray(ref["radius"], np.float64) / levels  # (W,)
        ref_own = np.asarray(ref["own"], np.float64)
        worst = 0.0
        rows = [(prog["own"][i], i) for i in range(len(prog["own"]))]
        rows += [(prog["copies"][r], int(prog["src"][r]))
                 for r in range(len(prog["copies"]))]
        for got, src in rows:
            off = np.abs(np.asarray(got, np.float64) - ref_own[src])
            worst = max(worst, float(np.mean(off > half_step[src])))
        out["level_gap"] = worst
    return out


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """correct iff every number is within its limit; the (number, limit)
    pairs in a fixed order."""
    missing = sorted(set(nums) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing}")
    shown = {k: {"value": nums[k], "limit": limits[k]} for k in sorted(nums)}
    return all(nums[k] <= limits[k] for k in nums), shown
