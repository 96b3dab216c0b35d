"""Plain reference of one Q-SGADMM round (paper Algorithm 1, eqs. 14-18).

Written from the paper and the deployment a traffic file states; it imports
nothing of the program.  W workers on a chain (worker i <-> i + 1, heads
at even positions).  A round:

  * gauss-seidel: the heads, then the tails, each run `local_iters` Adam
    steps on their augmented Lagrangian
        f(theta) + sum_nbr sign * <lam_e, theta - hat_nbr>
                 + rho / 2 * ||theta - hat_nbr||^2
    (sign +1 for a head, -1 for a tail), quantize theta - hat with the
    stochastic quantizer of eq. 7 on one global radius R = ||theta - hat||_inf
    over all parameters, and every receiver decodes the levels into its copy
    of the sender's hat;  jacobi: all workers in one phase.
  * the damped dual update of eq. 18 on every edge:
        lam_e += alpha * rho * (hat_head - hat_tail).

Randomness follows the trainer's documented convention: the state key is
the second half of split(seed key), each round splits it into (next, k1,
k2), and phase p draws uniform(k_p, (W, D)) over the worker-stacked flat
wire (parameter leaves in tree order).  The loss of a round is the mean over
the workers of the data loss at the start of the round.

Every array is kept in `dtype`: float32 for the reference (the caller sets
"highest" matmul precision), bfloat16 for the control.  `fault` plants one
of the faults the correctness check must catch:
  "unchanged":   the round returns its state unchanged;
  "half_batch":  the data loss is the mean over the first half of the rows;
  "no_exchange": receivers never get their neighbours' payloads.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
FAULTS = ("unchanged", "half_batch", "no_exchange")


class State(NamedTuple):
    """Per-worker and per-directed-edge lists of parameter trees (kept
    apart, so a round never holds a second stacked copy of the state)."""

    theta: list      # W trees
    opt_mu: list
    opt_nu: list
    opt_t: list      # W int32 scalars
    theta_hat: list  # W trees: each worker's committed hat
    hat_edge: list   # 2E trees: dst's copy of src's hat
    lam_edge: list   # 2E trees: dst's mirror of the edge dual
    radius: list     # W f32 scalars: the last radius each worker sent
    key: jax.Array


class Round:
    """The reference round for one traffic file's deployment."""

    def __init__(self, loss_fn, model_cfg: dict, dist: dict, dtype,
                 fault: str | None = None):
        if dist["topology"] != "chain":
            raise ValueError(f"reference round: topology {dist['topology']!r}")
        for k, v in (("staleness", 0), ("censor", None),
                     ("participation", 1.0), ("layerwise", None),
                     ("radius_mode", "global")):
            if dist[k] != v:
                raise ValueError(f"reference round: {k}={dist[k]!r}")
        if fault is not None and fault not in FAULTS:
            raise ValueError(fault)
        self.loss_fn, self.cfg, self.dist = loss_fn, model_cfg, dist
        self.dtype, self.fault = dtype, fault
        w = dist["num_workers"]
        self.w = w
        self.head = np.arange(w) % 2 == 0
        pairs = [(i, i + 1) for i in range(w - 1)]
        rows = sorted([(b, a) for a, b in pairs] + list(pairs),
                      key=lambda sd: (sd[1], sd[0]))
        self.src = np.array([s for s, _ in rows], np.int64)
        self.dst = np.array([d for _, d in rows], np.int64)
        self.sign_dst = np.where(self.head[self.dst], 1.0, -1.0).astype(
            np.float32)
        self.in_rows = [np.flatnonzero(self.dst == i) for i in range(w)]
        self.max_deg = max([1] + [len(r) for r in self.in_rows])
        self.levels = float((1 << dist["bits"]) - 1)
        self._update = jax.jit(self._worker_update)
        self._codec = jax.jit(self._quantize)

    # ------------------------------------------------------------ state --
    def init_state(self, params, k_state) -> State:
        w, rows = self.w, len(self.src)
        params = jax.tree.map(lambda a: a.astype(self.dtype), params)

        def zeros(n):
            return [jax.tree.map(jnp.zeros_like, params) for _ in range(n)]

        return State(theta=[params] * w, opt_mu=zeros(w), opt_nu=zeros(w),
                     opt_t=[jnp.zeros((), jnp.int32)] * w,
                     theta_hat=zeros(w), hat_edge=zeros(rows),
                     lam_edge=zeros(rows),
                     radius=[jnp.zeros((), jnp.float32)] * w, key=k_state)

    # ------------------------------------------------------------ local --
    def _data_loss(self, theta, batch):
        if self.fault == "half_batch":
            half = batch["tokens"].shape[0] // 2
            batch = jax.tree.map(lambda a: a[:half], batch)
        return self.loss_fn(theta, batch, self.cfg)

    def _worker_update(self, theta, mu, nu, t, batch, hats, lams, weight,
                       sign):
        """One worker's local Adam steps; hats/lams stacked over its
        (padded) neighbour slots, weight 1 for a real slot and 0 for pad."""
        rho = self.dist["rho"]

        def aug(th):
            f = self._data_loss(th, batch)
            extra = jnp.zeros((), jnp.float32)
            for c in range(weight.shape[0]):
                for a, h, l in zip(jax.tree.leaves(th),
                                   jax.tree.leaves(hats),
                                   jax.tree.leaves(lams)):
                    diff = a - h[c]
                    extra = extra + weight[c] * (
                        sign * jnp.sum(l[c] * diff)
                        + 0.5 * rho * jnp.sum(diff * diff)).astype(
                            jnp.float32)
            return f + extra, f

        f_first = None
        for _ in range(self.dist["local_iters"]):
            (_, f), g = jax.value_and_grad(aug, has_aux=True)(theta)
            f_first = f if f_first is None else f_first
            t = t + 1
            tf = t.astype(jnp.float32)
            mu = jax.tree.map(lambda m, gg: ADAM_B1 * m + (1 - ADAM_B1) * gg,
                              mu, g)
            nu = jax.tree.map(
                lambda v, gg: ADAM_B2 * v + (1 - ADAM_B2) * gg * gg, nu, g)
            c1 = (1 - ADAM_B1 ** tf).astype(self.dtype)
            c2 = (1 - ADAM_B2 ** tf).astype(self.dtype)
            theta = jax.tree.map(
                lambda th, m, v: th - self.dist["local_lr"] * (m / c1)
                / (jnp.sqrt(v / c2) + ADAM_EPS), theta, mu, nu)
        return theta, mu, nu, t, f_first

    # ------------------------------------------------------------ codec --
    def _quantize(self, theta, hat, u):
        """Stochastic quantizer (eq. 7) of theta - hat on one global radius;
        returns the decoded hat every receiver commits, and R."""
        leaves = jax.tree.leaves(theta)
        hats = jax.tree.leaves(hat)
        r = functools.reduce(jnp.maximum, [jnp.max(jnp.abs(a - h))
                                           for a, h in zip(leaves, hats)])
        r = r.astype(self.dtype)
        step = 2 * jnp.maximum(r, jnp.asarray(1e-30, self.dtype)) / self.levels
        out, off = [], 0
        for a, h in zip(leaves, hats):
            n = a.size
            uu = u[off:off + n].reshape(a.shape).astype(self.dtype)
            off += n
            c = (a - h + r) / step
            low = jnp.floor(c)
            q = jnp.clip(low + (uu < c - low).astype(self.dtype), 0,
                         self.levels)
            out.append(jnp.where(r > 0, h + step * q - r, h))
        return jax.tree.unflatten(jax.tree.structure(hat), out), r

    # ------------------------------------------------------------ round --
    def step(self, st: State, batch):
        """One round; batch leaves (W, B, ...).  Returns (state, {'loss'})."""
        w = self.w
        row = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
        if self.fault == "unchanged":
            f = [self._data_loss(st.theta[i], row(batch, i)) for i in range(w)]
            return st, {"loss": jnp.mean(jnp.stack(f))}
        key, k1, k2 = jax.random.split(st.key, 3)
        if self.dist["mode"] == "gauss-seidel":
            phases = [(self.head, k1), (~self.head, k2)]
        else:
            phases = [(np.ones(w, bool), k1)]
        d = sum(a.size for a in jax.tree.leaves(st.theta[0]))
        # the state's lists are updated in place, so a round never holds
        # two copies of the state
        theta, mu, nu, t = st.theta, st.opt_mu, st.opt_nu, st.opt_t
        hat, radius = st.theta_hat, st.radius
        hat_edge, lam_edge = st.hat_edge, st.lam_edge
        losses = [None] * w
        zero = jax.tree.map(jnp.zeros_like, theta[0])
        stack = lambda ts: jax.tree.map(lambda *a: jnp.stack(a), *ts)
        for active, k in phases:
            for i in np.flatnonzero(active):
                slots = list(self.in_rows[i])
                pad = self.max_deg - len(slots)
                weight = jnp.asarray([1.0] * len(slots) + [0.0] * pad,
                                     self.dtype)
                theta[i], mu[i], nu[i], t[i], losses[i] = self._update(
                    theta[i], mu[i], nu[i], t[i], row(batch, i),
                    stack([hat_edge[r] for r in slots] + [zero] * pad),
                    stack([lam_edge[r] for r in slots] + [zero] * pad),
                    weight,
                    jnp.asarray(1.0 if self.head[i] else -1.0, self.dtype))
            u = jax.random.uniform(k, (w, d), jnp.float32)
            for i in np.flatnonzero(active):
                if self.dist["quantize"]:
                    hat[i], radius[i] = self._codec(theta[i], hat[i], u[i])
                else:
                    hat[i] = theta[i]
            del u
            if self.fault != "no_exchange":
                for r in range(len(self.src)):
                    if active[self.src[r]]:
                        hat_edge[r] = hat[self.src[r]]
        scale = self.dist["alpha"] * self.dist["rho"]
        for r in range(len(self.src)):
            lam_edge[r] = self._dual(lam_edge[r], hat[self.dst[r]],
                                     hat_edge[r],
                                     jnp.asarray(scale * self.sign_dst[r],
                                                 self.dtype))
        radius[:] = [jnp.asarray(r, jnp.float32) for r in radius]
        return st._replace(key=key), {"loss": jnp.mean(jnp.stack(
            [jnp.asarray(f, jnp.float32) for f in losses]))}

    @functools.partial(jax.jit, static_argnums=0)
    def _dual(self, lam, own, nbr, coef):
        return jax.tree.map(lambda l, a, b: l + coef * (a - b), lam, own, nbr)
