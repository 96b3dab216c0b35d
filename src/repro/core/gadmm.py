"""GADMM and Q-GADMM for convex objectives on a worker chain (Algorithm 1).

Faithful implementation of paper eqs. (14)-(18):

  per iteration k:
    heads  (chain pos 0,2,4,..): theta_n^{k+1} = argmin f_n + duals + prox to
                                 the *reconstructed* neighbor models hat_theta
    heads quantize (theta^{k+1} - hat_theta^k) and transmit (b, R, q)
    tails  (pos 1,3,5,..):        same, using heads' fresh hat_theta^{k+1}
    tails quantize + transmit
    all:   lambda_n^{k+1} = lambda_n^k + rho (hat_theta_n - hat_theta_{n+1})

The local problems here are quadratics f_n(t) = 0.5 ||X_n t - y_n||^2, solved in
closed form:  (X^T X + c_n rho I) t = X^T y + lam_{n-1} - lam_n
                                       + rho (hat_{n-1} + hat_{n+1})
with c_n = #neighbors.  The whole chain updates are vectorized over workers and
the iteration is jit-compiled (lax-friendly: masks instead of python branches).

With cfg.quantize=False this is exactly GADMM [23] (hat_theta == theta).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .quantizer import QuantizerConfig, _next_bits, header_bits, levels_of

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class GADMMConfig:
    rho: float = 24.0
    quantize: bool = True
    qcfg: QuantizerConfig = QuantizerConfig(bits=2)
    alpha: float = 1.0  # dual damping (paper uses 1 for convex, 0.01 for DNN)
    topk_frac: float = 1.0  # beyond-paper: transmit only the top-k fraction
                            # of |delta| coords per round.  Unsent coords keep
                            # their old hat value, so their residual stays in
                            # theta - hat and is retransmitted later — the
                            # hat-difference scheme IS error feedback.


class ChainState(NamedTuple):
    theta: Array       # (N, d) current primal variables
    theta_hat: Array   # (N, d) last *quantized* model of every worker, as known
                       # by its neighbors (== sender's own copy; kept in sync)
    lam: Array         # (N+1, d) duals; lam[0] == lam[N] == 0 always
    radius: Array      # (N,) R_n^{k-1}
    bits: Array        # (N,) b_n^{k-1}
    key: Array
    step: Array


def init_state(n: int, d: int, cfg: GADMMConfig, seed: int = 0) -> ChainState:
    return ChainState(
        theta=jnp.zeros((n, d)),
        theta_hat=jnp.zeros((n, d)),
        lam=jnp.zeros((n + 1, d)),
        radius=jnp.zeros((n,)),
        bits=jnp.full((n,), cfg.qcfg.bits, jnp.int32),
        key=jax.random.PRNGKey(seed),
        step=jnp.zeros((), jnp.int32),
    )


class Quadratic(NamedTuple):
    """Per-worker quadratic local objectives, pre-factorized for both c values."""

    xtx: Array      # (N, d, d)
    xty: Array      # (N, d)
    minv: Array     # (N, d, d): inverse of (xtx + c_n rho I), c_n = #neighbors
    def objective(self, theta: Array) -> Array:
        """F(theta) = sum_n 0.5 theta^T XtX theta - xty.theta + const.

        (const = 0.5 ||y||^2 is added by the caller if absolute values matter.)
        """
        quad = 0.5 * jnp.einsum("nd,nde,ne->", theta, self.xtx, theta)
        lin = jnp.einsum("nd,nd->", theta, self.xty)
        return quad - lin


def make_quadratic(xs: Array, ys: Array, rho: float) -> Quadratic:
    """xs: (N, m, d) worker design matrices, ys: (N, m)."""
    n, _, d = xs.shape
    xtx = jnp.einsum("nmd,nme->nde", xs, xs)
    xty = jnp.einsum("nmd,nm->nd", xs, ys)
    cn = jnp.where((jnp.arange(n) == 0) | (jnp.arange(n) == n - 1), 1.0, 2.0)
    eye = jnp.eye(d)
    minv = jnp.linalg.inv(xtx + rho * cn[:, None, None] * eye[None])
    return Quadratic(xtx=xtx, xty=xty, minv=minv)


def _solve_all(q: Quadratic, lam: Array, hat: Array, rho: float) -> Array:
    """Closed-form local argmin for every worker given current duals + hats."""
    n, d = hat.shape
    has_left = (jnp.arange(n) > 0)[:, None]
    has_right = (jnp.arange(n) < n - 1)[:, None]
    hat_left = jnp.roll(hat, 1, axis=0) * has_left
    hat_right = jnp.roll(hat, -1, axis=0) * has_right
    rhs = q.xty + lam[:-1] - lam[1:] + rho * (hat_left + hat_right)
    return jnp.einsum("nde,ne->nd", q.minv, rhs)


def dequantize_rows(qlev: Array, hat_prev: Array, radius: Array,
                    bits: Array) -> Array:
    """Receiver-side reconstruction of per-row payloads (eq. 13).

    The EXACT arithmetic quantize_rows applies on the sender — the sim's
    event-driven receivers (repro.sim.worker) reconstruct through this
    function, so both ends of a link stay bit-identical by construction.
    qlev: (..., d) levels, hat_prev: (..., d), radius/bits: (...,) per row.
    """
    levels = levels_of(bits)
    safe_r = jnp.maximum(radius, 1e-30)[..., None]
    step = 2.0 * safe_r / levels[..., None]
    hat_new = hat_prev + step * qlev - radius[..., None]
    return jnp.where(radius[..., None] > 0, hat_new, hat_prev)


def quantize_rows(theta: Array, hat_prev: Array, active: Array, key: Array,
                  radius_prev: Array, bits_prev: Array, cfg: GADMMConfig):
    """Stochastically quantize each active worker's row.

    Returns (hat_new, radius, bits, qlev) — qlev is the (N, d) wire payload
    (quantization levels); hat_new is its reconstruction via dequantize_rows
    (sender == receiver bit-sync).  Row n of every output depends ONLY on
    row n of the inputs (plus the shared key), so a single worker's
    transmission is reproducible in isolation — the property the
    event-driven simulator's actors (repro.sim) rely on.
    """
    n, d = theta.shape
    diff = theta - hat_prev
    r_new = jnp.max(jnp.abs(diff), axis=1)  # (N,) per-worker inf-norm
    # eq. 11 bit growth: single source of truth in quantizer._next_bits
    # (same dedup pattern as header_bits for the payload accounting).
    b_new = jnp.broadcast_to(
        _next_bits(cfg.qcfg, bits_prev, r_new, radius_prev), (n,))
    levels = levels_of(b_new)
    safe_r = jnp.maximum(r_new, 1e-30)[:, None]
    step = 2.0 * safe_r / levels[:, None]
    c = (diff + r_new[:, None]) / step
    low = jnp.floor(c)
    p = c - low
    u = jax.random.uniform(key, (n, d))
    qlev = jnp.clip(low + (u < p), 0.0, levels[:, None])
    hat_new = dequantize_rows(qlev, hat_prev, r_new, b_new)
    if cfg.topk_frac < 1.0:
        # sparsify: exactly the k largest |delta| coords are transmitted (ties
        # broken by index, matching the billed k of bits_per_round); the rest
        # keep the receiver's (== sender's) previous hat value.
        k = max(int(d * cfg.topk_frac), 1)
        _, top_idx = jax.lax.top_k(jnp.abs(diff), k)  # (N, k)
        sent = jnp.zeros((n, d), bool).at[
            jnp.arange(n)[:, None], top_idx].set(True)
        hat_new = jnp.where(sent, hat_new, hat_prev)
    if not cfg.quantize:
        hat_new = theta  # GADMM: full precision "transmission"
    hat = jnp.where(active[:, None], hat_new, hat_prev)
    return (hat,
            jnp.where(active, r_new, radius_prev),
            jnp.where(active, b_new, bits_prev),
            qlev)


def _quantize_rows(theta: Array, hat_prev: Array, active: Array, key: Array,
                   radius_prev: Array, bits_prev: Array, cfg: GADMMConfig):
    """quantize_rows without the wire payload (chain/sgadmm call sites)."""
    hat, radius, bits, _ = quantize_rows(theta, hat_prev, active, key,
                                         radius_prev, bits_prev, cfg)
    return hat, radius, bits


def gadmm_step(state: ChainState, q: Quadratic, cfg: GADMMConfig) -> ChainState:
    """One full iteration (heads phase + tails phase + dual update)."""
    n, d = state.theta.shape
    idx = jnp.arange(n)
    is_head = (idx % 2 == 0)
    key, k_h, k_t = jax.random.split(state.key, 3)

    # --- heads phase ---
    theta_all = _solve_all(q, state.lam, state.theta_hat, cfg.rho)
    theta = jnp.where(is_head[:, None], theta_all, state.theta)
    hat, radius, bits = _quantize_rows(
        theta, state.theta_hat, is_head, k_h, state.radius, state.bits, cfg)

    # --- tails phase (uses heads' fresh hats) ---
    theta_all = _solve_all(q, state.lam, hat, cfg.rho)
    theta = jnp.where(is_head[:, None], theta, theta_all)
    hat, radius, bits = _quantize_rows(
        theta, hat, ~is_head, k_t, radius, bits, cfg)

    # --- dual update (eq. 18), computed from reconstructed hats ---
    resid = hat[:-1] - hat[1:]                      # (N-1, d)
    lam = state.lam.at[1:-1].add(cfg.alpha * cfg.rho * resid[: n - 1])
    lam = lam.at[0].set(0.0).at[-1].set(0.0)

    return ChainState(theta=theta, theta_hat=hat, lam=lam, radius=radius,
                      bits=bits, key=key, step=state.step + 1)


def rechain(state: ChainState, perm) -> ChainState:
    """Time-varying topology (paper Sec. II: GADMM converges under changing
    neighbors).  `perm[i]` = worker that moves to chain position i.  Primal
    state travels with the worker; edge duals are position-bound and are
    reset (a safe ADMM restart — stale duals for new edges would bias the
    first updates).  Quantizer sync state (theta_hat) also travels: both
    neighbors of any new edge reconstruct from the worker's own hat history,
    which is globally consistent by construction."""
    import jax.numpy as jnp

    perm = jnp.asarray(perm)
    return state._replace(
        theta=state.theta[perm],
        theta_hat=state.theta_hat[perm],
        lam=jnp.zeros_like(state.lam),
        radius=state.radius[perm],
        bits=state.bits[perm],
    )


def rechain_quadratic(q: Quadratic, perm, rho: float) -> Quadratic:
    """Permute per-position objectives for a new chain order and refactor
    (endpoint positions have c_n = 1, interior c_n = 2)."""
    import jax.numpy as jnp

    perm = jnp.asarray(perm)
    xtx = q.xtx[perm]
    xty = q.xty[perm]
    n, d = xty.shape
    cn = jnp.where((jnp.arange(n) == 0) | (jnp.arange(n) == n - 1), 1.0, 2.0)
    minv = jnp.linalg.inv(xtx + rho * cn[:, None, None] * jnp.eye(d)[None])
    return Quadratic(xtx=xtx, xty=xty, minv=minv)


def residuals(state: ChainState) -> tuple[Array, Array]:
    """Primal residual ||theta_n - theta_{n+1}|| (consensus violation) and a
    dual-residual proxy ||hat^k - hat^{k-1}|| is tracked by the caller."""
    r = state.theta[:-1] - state.theta[1:]
    return jnp.sqrt(jnp.sum(r * r)), jnp.max(jnp.abs(r))


def _payload_bits_per_worker(cfg: GADMMConfig, d: int) -> int:
    """Bits of one worker's broadcast payload (shared by the chain and graph
    accounting)."""
    if cfg.quantize:
        header = header_bits(cfg.qcfg.adapt_bits)
        if cfg.topk_frac < 1.0:
            import math

            k = max(int(d * cfg.topk_frac), 1)
            idx_bits = max(int(math.ceil(math.log2(max(d, 2)))), 1)
            return k * (cfg.qcfg.bits + idx_bits) + header
        return cfg.qcfg.bits * d + header
    return 32 * d


def bits_per_round(cfg: GADMMConfig, n: int, d: int) -> int:
    """Total bits all N workers transmit in one iteration.

    Q-GADMM payload per worker = b*d + header, with the header shared with
    quantizer.payload_bits (quantizer.header_bits: the R f32 and the b i32
    the payload always carries — 64 + b*d for fixed global-radius bits).
    """
    return n * _payload_bits_per_worker(cfg, d)


# ===== generalized topologies + censored transmissions (CQ-GGADMM) =========
#
# The chain implementation above is the paper-faithful fast path.  The graph
# variant below runs the same two-phase Gauss-Seidel sweep on ANY connected
# bipartite topology (core.topology: ring / star / 2d-torus / arbitrary),
# with one dual variable per EDGE instead of per chain link, and optional
# censored transmissions (core.censor): a worker whose freshly quantized
# model moved less than tau*xi^k keeps silent — every endpoint (itself
# included) reuses the previous hat, so sender==receiver bit-sync survives.
# It is the single-host reference the distributed trainer's topology/censor
# modes are validated against (tests/test_convergence.py).


class GraphState(NamedTuple):
    theta: Array       # (N, d) current primals
    theta_hat: Array   # (N, d) last *transmitted* quantized models
    lam: Array         # (E, d) edge duals, canonical head -> tail
    radius: Array      # (N,) R_n of the last transmitted round
    bits: Array        # (N,) b_n of the last transmitted round
    sent: Array        # (N,) bool: did worker n transmit last iteration?
    key: Array
    step: Array


def graph_init_state(topo, d: int, cfg: GADMMConfig,
                     seed: int = 0) -> GraphState:
    n = topo.n
    return GraphState(
        theta=jnp.zeros((n, d)),
        theta_hat=jnp.zeros((n, d)),
        lam=jnp.zeros((topo.num_edges, d)),
        radius=jnp.zeros((n,)),
        bits=jnp.full((n,), cfg.qcfg.bits, jnp.int32),
        sent=jnp.zeros((n,), bool),
        key=jax.random.PRNGKey(seed),
        step=jnp.zeros((), jnp.int32),
    )


def make_graph_quadratic(xs: Array, ys: Array, rho: float, topo) -> Quadratic:
    """Per-worker quadratics factored with c_n = deg(n) from the topology."""
    n, _, d = xs.shape
    assert n == topo.n, (n, topo.n)
    xtx = jnp.einsum("nmd,nme->nde", xs, xs)
    xty = jnp.einsum("nmd,nm->nd", xs, ys)
    cn = jnp.asarray(topo.degree, jnp.float32)
    eye = jnp.eye(d)
    minv = jnp.linalg.inv(xtx + rho * cn[:, None, None] * eye[None])
    return Quadratic(xtx=xtx, xty=xty, minv=minv)


def graph_consts(topo, layout: str = "edge"):
    """Static jnp views of the topology used inside the jitted step.

    Always carries the O(E) directed edge-index arrays from
    ``topology.edge_index`` (``d_src``/``d_dst``/``d_edge``, sorted by
    (dst, src)).  The dense port-style operators (``adj``, ``inc`` —
    O(N^2) / O(N*E) memory and aggregation work) are materialized only
    when ``layout='port'`` asks for them: at production worker counts
    (10^4+) the dense matrices alone would dwarf the model state, and the
    edge layout never touches them.  The two layouts are
    bitwise-identical on CPU (property-tested in tests/test_gadmm.py)
    because the segment_sum adds each worker's neighbor terms in the same
    ascending order the dense row reduction uses."""
    import numpy as np

    from .topology import edge_index

    n = topo.n
    eidx = edge_index(topo)
    tc = dict(
        head=jnp.asarray(topo.head_mask),
        adj=None,
        inc=None,
        e_head=jnp.asarray(topo.edges[:, 0] if topo.num_edges else
                           np.zeros((0,), np.int64)),
        e_tail=jnp.asarray(topo.edges[:, 1] if topo.num_edges else
                           np.zeros((0,), np.int64)),
        n=n,
        d_src=jnp.asarray(eidx.src),
        d_dst=jnp.asarray(eidx.dst),
        d_edge=jnp.asarray(eidx.edge),
    )
    if layout == "port":
        inc = np.zeros((n, max(topo.num_edges, 1)), np.float32)
        for e, (h, t) in enumerate(topo.edges):
            inc[h, e] = inc[t, e] = 1.0
        tc["adj"] = jnp.asarray(topo.adjacency(), jnp.float32)
        tc["inc"] = jnp.asarray(inc)
    return tc


_graph_consts = graph_consts  # pre-PR-4 name


def _graph_solve_all(q: Quadratic, lam: Array, hat: Array, rho: float,
                     tc, layout: str = "edge") -> Array:
    """Closed-form local argmin for every worker on the graph.

    Node n minimizes f_n + s_n * sum_e<n> <lam_e, theta_n - hat_nbr> +
    rho/2 sum_nbr ||theta_n - hat_nbr||^2 with s_n = +1 for heads (the edge
    dual's canonical orientation is head -> tail), giving
      (XtX + deg_n rho I) theta_n = Xty_n - s_n sum_e lam_e
                                    + rho sum_nbr hat_nbr.

    layout='edge' (default) aggregates the neighbor sums with one
    segment_sum over the 2E directed edges — O(E*d) work.  layout='port'
    is the pre-refactor dense form (inc @ lam, adj @ hat — O(N*E*d) /
    O(N^2*d)), kept as the comparator for the bitwise-equivalence
    property test and the benchmark baseline.
    """
    sign = jnp.where(tc["head"], 1.0, -1.0)[:, None]
    if layout == "port":
        assert tc["adj"] is not None, \
            "layout='port' needs graph_consts(topo, layout='port')"
        lam_sum = tc["inc"] @ lam if lam.shape[0] else jnp.zeros_like(hat)
        nbr_sum = tc["adj"] @ hat
    else:
        assert layout == "edge", layout
        n = tc["n"]
        if lam.shape[0]:
            # directed edges sorted by (dst, src): worker n's terms are
            # added in ascending neighbor order, matching the dense row
            # reduction bit for bit on CPU
            lam_sum = jax.ops.segment_sum(lam[tc["d_edge"]], tc["d_dst"],
                                          num_segments=n,
                                          indices_are_sorted=True)
            nbr_sum = jax.ops.segment_sum(hat[tc["d_src"]], tc["d_dst"],
                                          num_segments=n,
                                          indices_are_sorted=True)
        else:
            # degenerate graphs (W=1): no edges, no neighbor terms
            lam_sum = jnp.zeros_like(hat)
            nbr_sum = jnp.zeros_like(hat)
    rhs = q.xty - sign * lam_sum + rho * nbr_sum
    return jnp.einsum("nde,ne->nd", q.minv, rhs)


def graph_phase(theta: Array, hat: Array, lam: Array, radius: Array,
                bits: Array, active: Array, key: Array, *, q: Quadratic,
                cfg: GADMMConfig, tc, step: Array, censor=None,
                layout: str = "edge"):
    """One phase of the graph sweep: the `active` group solves its local
    problems, quantizes, and (optionally) censors.

    Returns (theta, hat, radius, bits, sent, qlev).  Row n of every output
    depends only on row n of the inputs, n's neighbor rows of `hat`
    (through the adjacency-masked proximal term), and n's incident rows of
    `lam` — so a single worker can replay its own row exactly from a local
    view that has garbage in all unrelated rows.  This is the contract the
    event-driven simulator's actors (repro.sim.worker.GraphActor) build on:
    the lockstep graph_step below and the message-by-message simulator run
    the SAME function and are bit-identical under an ideal network.
    """
    from .censor import transmit_mask

    theta_all = _graph_solve_all(q, lam, hat, cfg.rho, tc, layout=layout)
    theta = jnp.where(active[:, None], theta_all, theta)
    hat_new, r_new, b_new, qlev = quantize_rows(
        theta, hat, active, key, radius, bits, cfg)
    if censor is not None:
        sent = active & transmit_mask(hat_new, hat, censor, step)
        hat_new = jnp.where(sent[:, None], hat_new, hat)
        r_new = jnp.where(sent, r_new, radius)
        b_new = jnp.where(sent, b_new, bits)
    else:
        sent = active
    return theta, hat_new, r_new, b_new, sent, qlev


def graph_dual_update(lam: Array, hat: Array, cfg: GADMMConfig, tc,
                      edge_mask: Array | None = None) -> Array:
    """Per-edge damped dual update (eq. 18): lam_e += a*rho*(h_head - h_tail).

    `edge_mask` (E,) freezes edges when 0 — the simulator uses it to stop
    updating duals on links whose far endpoint dropped out.
    """
    if not lam.shape[0]:
        return lam
    resid = hat[tc["e_head"]] - hat[tc["e_tail"]]
    if edge_mask is not None:
        resid = resid * edge_mask[:, None]
    return lam + cfg.alpha * cfg.rho * resid


def graph_step(state: GraphState, q: Quadratic, cfg: GADMMConfig, topo,
               censor=None, layout: str = "edge") -> GraphState:
    """One censored GGADMM/CQ-GGADMM iteration on an arbitrary bipartite
    topology (heads phase + tails phase + per-edge dual update).

    `censor` is an optional core.censor.CensorConfig; when set, a phase's
    freshly quantized hats are committed only for workers whose update
    clears the decaying threshold — everyone else's neighbors (and the
    worker itself) keep the previous hat, and the round is recorded in
    state.sent for wire accounting (graph_bits_per_round).

    `layout` selects the neighbor-aggregation state layout: 'edge' (the
    O(E) segment_sum default) or 'port' (pre-refactor dense operators) —
    bitwise-identical on CPU, property-tested in tests/test_gadmm.py.
    """
    tc = graph_consts(topo, layout=layout)
    is_head = tc["head"]
    key, k_h, k_t = jax.random.split(state.key, 3)

    theta, hat, radius, bits, sent_h, _ = graph_phase(
        state.theta, state.theta_hat, state.lam, state.radius, state.bits,
        is_head, k_h, q=q, cfg=cfg, tc=tc, step=state.step, censor=censor,
        layout=layout)
    theta, hat, radius, bits, sent_t, _ = graph_phase(
        theta, hat, state.lam, radius, bits,
        ~is_head, k_t, q=q, cfg=cfg, tc=tc, step=state.step, censor=censor,
        layout=layout)
    lam = graph_dual_update(state.lam, hat, cfg, tc)

    return GraphState(theta=theta, theta_hat=hat, lam=lam, radius=radius,
                      bits=bits, sent=sent_h | sent_t, key=key,
                      step=state.step + 1)


def graph_bits_per_round(cfg: GADMMConfig, topo, d: int,
                         sent=None, censored: bool = False):
    """Bits all workers transmit in one graph iteration (broadcast
    accounting, same per-worker payload rule as bits_per_round).

    Without censoring every worker broadcasts once; with censoring only the
    workers with sent=True pay the payload, everyone pays FLAG_BITS for the
    censor flag.  `sent` may be a traced (N,) bool array — the result is
    then a traced scalar, summable across rounds."""
    from .censor import FLAG_BITS

    per = _payload_bits_per_worker(cfg, d)
    if not censored:
        return topo.n * per
    assert sent is not None, "censored accounting needs the sent mask"
    return jnp.sum(sent.astype(jnp.float32)) * per + topo.n * FLAG_BITS
