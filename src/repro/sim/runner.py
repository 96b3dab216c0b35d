"""Entry points of the event-driven Q-GADMM runtime.

``simulate(xs, ys, gcfg, scfg)`` plays the CQ-GGADMM graph reference
(core.gadmm.graph_phase math) out message-by-message for a linear
regression problem; ``simulate_trainer(model, cfg, dcfg, batch, scfg)``
does the same for the distributed trainer's unsharded reference step
(dist.qgadmm.QGADMMTrainer).  Both build one shared jit-compiled function
table (one compilation serves all N actors), wire the actors to the
engine/network/timeline, run the event loop to quiescence, and return a
:class:`SimResult` with per-round assembled states (for the bit-parity
tests), an objective trace, and the timeline's wall-clock/Joules
accounting.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gadmm, quantizer
from repro.core.censor import FLAG_BITS, CensorConfig
from repro.core.comm_model import RadioConfig
from repro.core.topology import (DENSE_PLACEMENT_MAX, Placement, Topology,
                                 build_topology)

from .engine import Engine
from .network import ComputeModel, FaultPlan, Network, NetworkConfig
from .timeline import Timeline
from .worker import GraphActor, TrainerActor


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Scenario description for one simulator run.

    topology:  core.topology kind name or explicit Topology.
    rounds:    GADMM rounds each worker attempts to complete.
    staleness: 0 = barriered (bit-identical to the lockstep references
               under an ideal network); S > 0 allows every worker to run
               up to S rounds ahead of its slowest neighbor, computing
               against the freshest hats it has (bounded-staleness async).
    seed:      placement positions + every channel/compute draw.
    participation: per-(round, worker) Bernoulli rate of taking part in a
               round (1.0 = everyone, the default).  The schedule is drawn
               once at setup from default_rng([seed, 13]) and shared by
               every worker — an absent worker skips compute/transmit/dual
               for that round (it still listens), neighbors advance over
               its absence without a message, and an edge's dual updates
               only when BOTH endpoints participate.
    engine:    'events' = the per-message event loop (the bitwise oracle);
               'vectorized' = the large-N fast path (sim.vectorized) —
               identical states, batched timing (graph mode, staleness 0).
    """

    topology: Any = "chain"
    rounds: int = 100
    staleness: int = 0
    seed: int = 0
    radio: RadioConfig = RadioConfig()
    network: NetworkConfig = NetworkConfig()
    compute: ComputeModel = ComputeModel()
    faults: FaultPlan = FaultPlan()
    record_states: bool = True
    max_events: int | None = None
    participation: float = 1.0
    engine: str = "events"

    def __post_init__(self):
        assert self.engine in ("events", "vectorized"), self.engine
        assert 0.0 < self.participation <= 1.0, self.participation

    def event_budget(self, topo: Topology) -> int:
        """Liveness budget for Engine.run, scaled by the scenario.

        Per round the loop fires <= N compute completions + 2E deliveries
        (+1 for the odd engine bookkeeping event); retransmissions
        serialize inside a delivery's schedule and add no events, but
        lossy runs get extra slack for the bounded-retransmit tail.
        Membership churn (drops with their peer-down notifications, late
        joins) adds a per-worker term on top.
        """
        if self.max_events is not None:
            return self.max_events
        per_round = topo.n + 2 * topo.num_edges + 1
        slack = 10
        if self.network.loss_prob > 0.0:
            slack += 2 + min(self.network.max_retransmits, 100) // 10
        churn = sum(int(topo.degree[int(w)]) + 1
                    for w in self.faults.drop_round)
        churn += 2 * len(self.faults.join_round)
        return slack * (self.rounds + 1) * per_round + 16 * churn + 1000


@dataclasses.dataclass
class SimResult:
    topo: Topology
    timeline: Timeline
    states: list[Any]           # per-round assembled states (or [])
    losses: np.ndarray          # |F(theta_k) - F*| per assembled round
    events: int
    fstar: float | None = None  # |F*| of the problem (graph mode only)

    def to_target(self, target: float) -> dict[str, float]:
        return self.timeline.to_target(list(self.losses), target)

    def to_rel_target(self, rel: float) -> dict[str, float]:
        """*-to-target at a RELATIVE objective gap (needs fstar)."""
        assert self.fstar is not None, "relative targets need graph mode"
        return self.to_target(rel * self.fstar)

    def final_rel_gap(self) -> float:
        assert self.fstar is not None and len(self.losses)
        return float(self.losses[-1]) / self.fstar

    def summary(self) -> dict:
        s = self.timeline.summary()
        s["events"] = self.events
        if len(self.losses):
            s["final_gap"] = float(self.losses[-1])
        return s


def grid_placement(n: int, seed: int, topo: Topology,
                   grid: float = 250.0) -> Placement:
    """The paper's uniform grid drop, carrying an externally built
    Topology (random_placement derives its own graph from the
    nearest-neighbor chain order, which is NOT the canonical
    build_topology graph the lockstep references use — parity needs the
    exact same Topology on both sides)."""
    rng = np.random.default_rng([seed, 11])
    pos = rng.uniform(0.0, grid, size=(n, 2))
    if n > DENSE_PLACEMENT_MAX:
        # large-N path: the full O(N^2) pairwise matrix is exactly what
        # the scale refactor removed — the PS pick degrades to
        # centroid-nearest (the sim never uses the PS baseline anyway)
        ps = int(np.argmin(np.linalg.norm(pos - pos.mean(axis=0), axis=1)))
        ps_dist = np.linalg.norm(pos - pos[ps], axis=1)
    else:
        dmat = np.linalg.norm(pos[None, :, :] - pos[:, None, :], axis=-1)
        ps = int(np.argmin(dmat.sum(axis=1)))
        ps_dist = dmat[ps]
    return Placement(
        positions=pos, chain=np.arange(n), ps_index=ps,
        chain_hop_dist=np.linalg.norm(pos[1:] - pos[:-1], axis=1),
        ps_dist=ps_dist, topology=topo)


def participation_schedule(scfg: SimConfig, n: int) -> np.ndarray | None:
    """(rounds, N) bool participation mask shared by both engines, or
    None when everyone participates every round.

    Bernoulli(participation) per (round, worker) from
    default_rng([seed, 13]) — a setup-time agreement like the key beacon,
    so each worker advances its neighbors over absent rounds without a
    message — AND'ed with the FaultPlan's arrival schedule (a worker that
    joins at round r sits out rounds 0..r-1)."""
    joins = scfg.faults.join_round
    if scfg.participation >= 1.0 and not joins:
        return None
    part = np.ones((scfg.rounds, n), bool)
    if scfg.participation < 1.0:
        rng = np.random.default_rng([scfg.seed, 13])
        part &= rng.uniform(size=(scfg.rounds, n)) < scfg.participation
    for w, r in joins.items():
        part[:int(r), int(w)] = False
    return part


def _beacon(key, rounds: int):
    """Precomputed per-round (head, tail) phase keys — the same split
    chain graph_step / the trainer step walk (a deterministic seed
    schedule every worker agreed on at setup; only senders consume it)."""
    keys = []
    for _ in range(rounds):
        key, k_h, k_t = jax.random.split(key, 3)
        keys.append((k_h, k_t))
    return keys


# ------------------------------------------------------------- graph mode --
def _graph_fns(q, cfg, tc, censor):
    """Shared jitted function table for GraphActor (one compile, N actors)."""

    @jax.jit
    def phase(theta, hat, lam, radius, bits, active, key, step, i):
        th, h, r, b, sent, qlev = gadmm.graph_phase(
            theta, hat, lam, radius, bits, active, key,
            q=q, cfg=cfg, tc=tc, step=step, censor=censor)
        return th, h, r, b, sent[i], qlev[i], h[i], r[i], b[i]

    @jax.jit
    def apply(hat, j, row):
        return hat.at[j].set(row)

    @jax.jit
    def dual(lam, hat, edge_mask):
        return gadmm.graph_dual_update(lam, hat, cfg, tc, edge_mask)

    @jax.jit
    def phase_full(theta, hat, lam, radius, bits, active, key, step):
        """Whole-phase update for the vectorized engine: one call per
        color group per round (active = phase mask & participation mask)
        instead of one per actor — graph_phase leaves inactive rows
        untouched, so the result is bitwise the actors' per-row commits."""
        return gadmm.graph_phase(theta, hat, lam, radius, bits, active, key,
                                 q=q, cfg=cfg, tc=tc, step=step,
                                 censor=censor)

    return {"phase": phase, "apply": apply, "dual": dual,
            "phase_full": phase_full}


def _build_world(scfg: SimConfig, topo: Topology, placement):
    engine = Engine()
    timeline = Timeline(topo.n)
    placement = placement or grid_placement(topo.n, scfg.seed, topo)
    network = Network(engine, topo, placement, scfg.radio, scfg.network,
                      timeline, seed=scfg.seed)
    return engine, timeline, network


def _run_world(engine, network, actors, scfg: SimConfig, topo: Topology):
    network.register(actors)
    for a in actors:
        a.start()
    events = engine.run(max_events=scfg.event_budget(topo))
    # a drained queue with unfinished live workers = protocol deadlock
    for a in actors:
        assert a.dropped or a.rnd >= scfg.rounds, (
            f"deadlock: worker {a.i} stuck at round {a.rnd}/{scfg.rounds} "
            f"(phase_done={a.phase_done}, nbr_round={a.nbr_round})")
    return events


def _assemble_graph_states(timeline: Timeline, state0, topo: Topology):
    """Stack per-worker snapshots into per-round GraphState-like views.
    Dropped workers contribute their last snapshot (frozen state)."""
    n = topo.n
    last = {w: None for w in range(n)}
    alive = [w for w in range(n) if w not in timeline.dropped_at]
    counted = alive if alive else list(range(n))
    k_max = min((len(timeline.round_done[w]) for w in counted), default=0)
    out = []
    for k in range(k_max):
        theta = np.asarray(state0.theta).copy()
        hat = np.asarray(state0.theta_hat).copy()
        lam = np.asarray(state0.lam).copy()
        radius = np.asarray(state0.radius).copy()
        bits = np.asarray(state0.bits).copy()
        sent = np.zeros((n,), bool)
        for w in range(n):
            snap = timeline.snapshots.get(k, {}).get(w, last[w])
            if snap is None:
                continue
            last[w] = snap
            theta[w] = snap["theta"]
            hat[w] = snap["hat"]
            radius[w] = snap["radius"]
            bits[w] = snap["bits"]
            sent[w] = snap["sent"]
            for e, row in snap["lam_rows"].items():
                lam[e] = row
        out.append(dict(theta=theta, theta_hat=hat, lam=lam, radius=radius,
                        bits=bits, sent=sent))
    return out


def simulate(xs, ys, gcfg: gadmm.GADMMConfig, scfg: SimConfig,
             censor: CensorConfig | None = None,
             placement: Placement | None = None) -> SimResult:
    """Event-driven CQ-GGADMM on per-worker quadratics (xs: (N, m, d),
    ys: (N, m)), reusing core.gadmm.graph_phase math actor-by-actor."""
    assert gcfg.topk_frac >= 1.0, \
        "top-k sparsification is not supported by the simulator"
    if scfg.engine == "vectorized":
        from .vectorized import simulate_vectorized
        return simulate_vectorized(xs, ys, gcfg, scfg, censor=censor,
                                   placement=placement)
    n, _, d = xs.shape
    topo = build_topology(scfg.topology, n)
    q = gadmm.make_graph_quadratic(xs, ys, gcfg.rho, topo)
    tc = gadmm.graph_consts(topo)
    state0 = gadmm.graph_init_state(topo, d, gcfg, seed=scfg.seed)
    fns = _graph_fns(q, gcfg, tc, censor)
    keys = _beacon(state0.key, scfg.rounds)
    payload_bits = gadmm._payload_bits_per_worker(gcfg, d)
    part = participation_schedule(scfg, n)

    engine, timeline, network = _build_world(scfg, topo, placement)
    actors = [
        GraphActor(
            i, topo, state0=state0, fns=fns, keys=keys, cfg=gcfg,
            payload_bits=payload_bits, flag_bits=FLAG_BITS,
            engine=engine, network=network, timeline=timeline,
            compute=scfg.compute, rounds=scfg.rounds,
            staleness=scfg.staleness, part=part,
            drop_round=scfg.faults.drops_at(i), seed=scfg.seed)
        for i in range(n)
    ]
    events = _run_world(engine, network, actors, scfg, topo)

    states = _assemble_graph_states(timeline, state0, topo) \
        if scfg.record_states else []
    fstar = _graph_fstar(q, xs, ys, d)
    if states:
        losses = np.asarray([abs(float(q.objective(jnp.asarray(s["theta"])))
                                 - fstar) for s in states])
    else:
        losses = np.zeros((0,))
    return SimResult(topo=topo, timeline=timeline, states=states,
                     losses=losses, events=events, fstar=abs(fstar))


def _graph_fstar(q, xs, ys, d: int) -> float:
    xtx = jnp.sum(q.xtx, axis=0)
    xty = jnp.sum(q.xty, axis=0)
    theta_star = jnp.linalg.solve(xtx, xty)
    n = q.xty.shape[0]
    return float(q.objective(jnp.broadcast_to(theta_star, (n, d))))


# ----------------------------------------------------------- trainer mode --
def _trainer_fns(trainer):
    """Shared jitted wrappers over the trainer's reference step pieces."""
    quantize = trainer.dcfg.gadmm.quantize

    @jax.jit
    def phase(st, batch, active, key, step, i):
        st2, payload, _ = trainer.phase_compute(st, batch, active, key, step)
        hat_row = jax.tree.map(lambda a: a[i], st2[1])
        if quantize:
            return (st2, payload["sent"][i], hat_row, payload["wire"][i],
                    payload["radius"][i], payload["bits"][i])
        return (st2, payload["sent"][i], hat_row, payload["wire"][i],
                jnp.zeros(()), jnp.zeros((), jnp.int32))

    @jax.jit
    def apply(st, s, row):
        """Store the partner's committed hat row at owner slot row s (the
        value the reference's in-program phase_apply reconstructs
        bit-identically; see TrainerActor._phase)."""
        (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t) = st
        hat_nbr = jax.tree.map(
            lambda a, r: a.at[s].set(r.astype(a.dtype)), hat_nbr, row)
        return (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t)

    @jax.jit
    def dual(st, edge_mask):
        return trainer.dual_update(st, edge_mask)

    return {"phase": phase, "apply": apply, "dual": dual}


def trainer_link_bits(trainer, d: int) -> float:
    """Per-directed-link payload bits, matching
    QGADMMTrainer.wire_bits_per_round's per-link term."""
    row_bits = 8 * trainer.wire_row_bytes(d)
    if trainer.dcfg.gadmm.quantize:
        n_r = (len(jax.tree.leaves(trainer.model.init(
            jax.random.PRNGKey(0), trainer.cfg)))
            if trainer.dcfg.radius_mode == "per_tensor" else 1)
        return row_bits + quantizer.header_bits(num_radii=n_r)
    return row_bits


def simulate_trainer(trainer, state0, batch, scfg: SimConfig,
                     placement: Placement | None = None) -> SimResult:
    """Event-driven replay of QGADMMTrainer's unsharded reference step.

    trainer: a QGADMMTrainer (gauss-seidel, overlap=False); its
    DistConfig.topology must equal scfg.topology.  state0: a DistState
    from dist.qgadmm.init_state.  The actors replay phase_compute /
    phase_apply / dual_update row-by-row; under an ideal network the
    per-round rows are bit-identical to make_train_step()
    (tests/test_sim.py)."""
    dcfg = trainer.dcfg
    assert dcfg.mode == "gauss-seidel" and not dcfg.overlap, \
        "the simulator models the two-phase gauss-seidel schedule"
    assert dcfg.staleness == 0, \
        "pass staleness via SimConfig: the simulator's per-message async " \
        "schedule subsumes the trainer's in-step pipeline"
    assert scfg.engine == "events", \
        "the vectorized engine covers graph mode only"
    assert scfg.participation >= 1.0 and not scfg.faults.join_round, \
        "partial participation in trainer mode lives in " \
        "DistConfig.participation (the in-step fold-in masks), not the sim"
    topo = trainer.topo
    assert build_topology(scfg.topology, dcfg.num_workers).kind == topo.kind
    d = sum(int(np.prod(l.shape[1:]))
            for l in jax.tree.leaves(state0.theta))
    fns = _trainer_fns(trainer)
    keys = _beacon(state0.key, scfg.rounds)
    st0 = (state0.theta, state0.theta_hat, state0.hat_nbr, state0.lam_nbr,
           state0.radius, state0.bits, state0.opt_mu, state0.opt_nu,
           state0.opt_t)

    engine, timeline, network = _build_world(scfg, topo, placement)
    actors = [
        TrainerActor(
            i, topo, st0=st0, batch=batch, fns=fns, keys=keys,
            trainer=trainer, payload_bits=trainer_link_bits(trainer, d),
            flag_bits=FLAG_BITS, engine=engine, network=network,
            timeline=timeline, compute=scfg.compute, rounds=scfg.rounds,
            staleness=scfg.staleness, drop_round=scfg.faults.drops_at(i),
            seed=scfg.seed)
        for i in range(dcfg.num_workers)
    ]
    events = _run_world(engine, network, actors, scfg, topo)
    states = []
    if scfg.record_states:
        k_max = min((len(timeline.round_done[w])
                     for w in range(dcfg.num_workers)), default=0)
        states = [
            {w: timeline.snapshots[k][w] for w in range(dcfg.num_workers)
             if w in timeline.snapshots.get(k, {})}
            for k in range(k_max)
        ]
    return SimResult(topo=topo, timeline=timeline, states=states,
                     losses=np.zeros((0,)), events=events)
