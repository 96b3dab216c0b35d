"""Q-GADMM actors for the event-driven runtime.

Each worker is a small protocol state machine around the *real* per-worker
update math — nothing numeric is reimplemented here:

  * :class:`GraphActor` replays ``core.gadmm.graph_phase`` /
    ``graph_dual_update`` (the CQ-GGADMM graph reference) on a local view,
  * :class:`TrainerActor` replays ``dist.qgadmm.QGADMMTrainer``'s
    ``phase_compute`` / ``phase_apply`` / ``dual_update`` methods (the
    unsharded reference step of the distributed trainer).

Local views.  Row n of every reference function depends only on row n of
its inputs plus n's neighbor rows of the hat state (through 0/1-masked
sums) — so an actor keeps a full-shaped *local view* whose own row and
neighbor rows are maintained exactly (neighbor rows only ever change by
applying received messages through the same reconstruction code the
lockstep reference runs) while all unrelated rows are don't-care.  Under
an ideal network this makes the actor's own row bit-identical to the
lockstep implementation, which tests/test_sim.py asserts per round.

Protocol (two-phase Gauss-Seidel, bounded staleness S = `staleness`):

  * a head may start its round-k phase once every live neighbor's last
    applied round >= k-1-S; a tail once every live head neighbor reached
    round k-S (S=0 is the barriered schedule: tails consume the heads'
    fresh round-k hats, exactly the lockstep sweep),
  * after its phase the worker broadcasts one payload — quantized levels
    + (R, b) sideband, or the 1-bit censor flag — through sim.network,
  * the worker completes round k (per-edge dual update, snapshot, k+1)
    once its own phase is done and every live neighbor's applied round
    >= k-S.

Messages on one directed link are applied strictly in round order (the
channel is FIFO and the actor buffers anything early) because the
quantizer is delta-coded: reconstruction of round k+1 requires the hat
state after round k.  Dropped neighbors are detected via the network's
peer-down notification; the actor stops waiting on them and freezes the
shared edge's dual instead of integrating a stale residual forever.

Async duals (S > 0).  Mixing the worker's *current* hat with whatever
neighbor round happens to be applied makes the two endpoints of an edge
integrate different residuals and their dual mirrors drift apart — the
old behaviour froze such edges, which silences the duals entirely once
the schedule is latency-bound and shifts the fixed point.  Instead each
actor keeps an S-deep history of its own committed hat row and of every
neighbor's reconstructed row, and the round-k dual step uses the
*common round* k-S snapshot of both endpoints (the completion gate
guarantees round k-S is applied).  This is exactly the
``DistConfig.staleness`` pipeline's dual rule (dist.qgadmm._stale_round:
``hat_lag`` vs the S-stale neighbor slots), so a latency-bound async run
and the trainer's in-step pipeline share a fixed point.  S=0 keeps the
original fresh-edge mask path bit-for-bit.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def _set_row(tree, idx, row):
    """Functional row write: `tree` with stacked-dim row `idx` <- `row`."""
    return jax.tree.map(lambda a, r: a.at[idx].set(r.astype(a.dtype)),
                        tree, row)


@dataclasses.dataclass
class Msg:
    src: int
    rnd: int
    sent: bool          # False = censor flag only
    body: dict[str, Any]
    bits: float


class BaseActor:
    """Shared Gauss-Seidel protocol machine; numeric hooks in subclasses:

    _phase(key) -> (sent: bool, body: dict, payload_bits: float)
    _apply(j, msg) -> None           (fold a neighbor's payload in)
    _dual_update() -> None
    _snapshot() -> dict
    """

    def __init__(self, i, topo, *, engine, network, timeline, compute,
                 rounds, staleness=0, drop_round=None, seed=0, part=None):
        self.i = int(i)
        self.topo = topo
        self.engine = engine
        self.network = network
        self.timeline = timeline
        self.compute = compute
        self.rounds = int(rounds)
        self.staleness = int(staleness)
        self.drop_round = drop_round
        self.is_head = bool(topo.head_mask[self.i])
        self.neighbors = [int(j) for j in topo.neighbors(self.i)]
        self.rng = np.random.default_rng([seed, 3, self.i])
        # (rounds, N) bool participation schedule, or None = everyone every
        # round.  The schedule is agreed at setup (like the key beacon), so
        # every worker can advance a neighbor's round over its absent
        # rounds without a message.
        self.part = part

        self.rnd = 0
        self.phase_done = False
        self.computing = False
        self.dropped = False
        self.radio_busy = 0.0
        self.nbr_round = {j: -1 for j in self.neighbors}
        self.dead: set[int] = set()
        self._early: dict[int, dict[int, Msg]] = {j: {} for j in self.neighbors}
        self.sent_log: list[bool] = []

    # ------------------------------------------------------------ schedule --
    def start(self) -> None:
        for j in self.neighbors:
            self._advance_absent(j)
        self._try_phase()

    def _live(self):
        return (j for j in self.neighbors if j not in self.dead)

    def _participates(self, rnd: int, w: int | None = None) -> bool:
        if self.part is None:
            return True
        w = self.i if w is None else w
        return rnd >= self.rounds or bool(self.part[rnd, w])

    def _advance_absent(self, j: int) -> None:
        """Advance neighbor j's applied round over its scheduled absences
        (no message exists for those rounds; j's hat is unchanged there,
        which is exactly what _post_advance records for the lag history)."""
        while (self.nbr_round[j] + 1 < self.rounds
               and not self._participates(self.nbr_round[j] + 1, j)):
            self.nbr_round[j] += 1
            self._post_advance(j, self.nbr_round[j])

    def _phase_ready(self) -> bool:
        need = self.rnd - 1 - self.staleness if self.is_head \
            else self.rnd - self.staleness
        return all(self.nbr_round[j] >= need for j in self._live())

    def _complete_ready(self) -> bool:
        need = self.rnd - self.staleness
        return all(self.nbr_round[j] >= need for j in self._live())

    def _try_phase(self) -> None:
        if self.dropped or self.computing or self.phase_done \
                or self.rnd >= self.rounds:
            return
        # absent rounds (partial participation / pre-join): no compute, no
        # transmission, no dual — complete instantly.  Neighbors advance
        # over these rounds from the shared schedule (_advance_absent).
        while (self.rnd < self.rounds
               and not (self.drop_round is not None
                        and self.rnd >= self.drop_round)
               and not self._participates(self.rnd)):
            self.sent_log.append(False)
            self._skip_hook()
            self.timeline.record_round(self.i, self.rnd, self.engine.now)
            self.timeline.record_snapshot(self.i, self.rnd, self._snapshot())
            self.rnd += 1
            for j in self._live():
                self._drain(j)
        if self.rnd >= self.rounds:
            return
        if self.drop_round is not None and self.rnd >= self.drop_round:
            self.dropped = True
            self.network.announce_drop(self.i)
            return
        if not self._phase_ready():
            return
        self.computing = True
        t_start = max(self.engine.now, self.radio_busy)
        dt = self.compute.sample(self.i, self.rng)
        self.engine.at(t_start + dt, self._on_compute_done)

    def _on_compute_done(self) -> None:
        key = self._phase_key()
        sent, body, bits = self._phase(key)
        self.sent_log.append(bool(sent))
        msg = Msg(src=self.i, rnd=self.rnd, sent=bool(sent), body=body,
                  bits=float(bits))
        self.radio_busy = self.network.broadcast(self.i, float(bits), msg)
        self.computing = False
        self.phase_done = True
        self._try_complete()

    def _try_complete(self) -> None:
        if self.dropped or not self.phase_done or not self._complete_ready():
            return
        self._dual_update()
        self.timeline.record_round(self.i, self.rnd, self.engine.now)
        self.timeline.record_snapshot(self.i, self.rnd, self._snapshot())
        self.rnd += 1
        self.phase_done = False
        for j in self._live():
            self._drain(j)
        self._try_phase()

    # ------------------------------------------------------------ receiving --
    def on_message(self, msg: Msg) -> None:
        if self.dropped:
            return
        # delta-coded payloads apply strictly in round order; the FIFO
        # channel makes out-of-order arrival impossible, the buffer keeps
        # the invariant explicit (and guards any future transport).
        self._early[msg.src][msg.rnd] = msg
        self._drain(msg.src)
        self._try_phase()
        self._try_complete()

    def _drain(self, j: int) -> None:
        """Fold neighbor j's buffered payloads in, up to round rnd+S.

        The round-k dual must see round-k mirrors, so a payload for a
        FUTURE round stays buffered until this worker's own round catches
        up (drained again on every round advance).  Without partial
        participation the gate is a no-op — the barrier never lets a
        neighbor's round exceed rnd+S — but an absence schedule releases
        neighbors early (skip-advance), and their round-(k+1) payload
        must not commit into a mirror my round-k dual still reads."""
        while (self.nbr_round[j] + 1 in self._early[j]
               and self.nbr_round[j] + 1 <= self.rnd + self.staleness):
            m = self._early[j].pop(self.nbr_round[j] + 1)
            if m.sent:
                self._apply(j, m)
            self.nbr_round[j] += 1
            self._post_advance(j, m.rnd)
            self._advance_absent(j)

    def on_peer_down(self, j: int) -> None:
        if self.dropped or j in self.dead:
            return
        self.dead.add(int(j))
        self._peer_down_hook(int(j))
        self._try_phase()
        self._try_complete()

    # ---------------------------------------------------------------- hooks --
    def _phase_key(self):
        raise NotImplementedError

    def _phase(self, key):
        raise NotImplementedError

    def _apply(self, j: int, msg: Msg) -> None:
        raise NotImplementedError

    def _post_advance(self, j: int, rnd: int) -> None:
        """Called after neighbor j's round-`rnd` message is folded in
        (sent or censored) — subclasses record lag history here."""

    def _skip_hook(self) -> None:
        """Called when an absent round completes instantly — subclasses
        record the (unchanged) own-row lag history here."""

    def _dual_update(self) -> None:
        raise NotImplementedError

    def _snapshot(self) -> dict:
        raise NotImplementedError

    def _peer_down_hook(self, j: int) -> None:
        pass


class GraphActor(BaseActor):
    """Actor running core.gadmm.graph_phase on a local view.

    `fns` is the shared jitted function table built once by the runner
    (sim.runner._graph_fns): phase / apply / dual — one compilation for
    all N actors.
    """

    def __init__(self, i, topo, *, state0, fns, keys, cfg, payload_bits,
                 flag_bits, **kw):
        super().__init__(i, topo, **kw)
        self.fns = fns
        self.keys = keys          # (rounds, 2, key) beacon: [k][head?0:1]
        self.cfg = cfg
        self.payload_bits = float(payload_bits)
        self.flag_bits = float(flag_bits)
        self.theta = state0.theta
        self.hat = state0.theta_hat
        self.lam = state0.lam
        self.radius = state0.radius
        self.bits = state0.bits
        self.active = jnp.asarray(topo.head_mask if self.is_head
                                  else ~topo.head_mask)
        self.edge_alive = np.ones((topo.num_edges,), np.float32)
        self._edge_of = topo.edge_lookup(self.i)
        # S-deep lag histories for the async common-round dual (module
        # docstring): round -> committed own row / reconstructed nbr row
        self._own_hist: dict[int, Any] = {}
        self._nbr_hist: dict[int, dict[int, Any]] = \
            {j: {} for j in self.neighbors}

    def _phase_key(self):
        return self.keys[self.rnd][0 if self.is_head else 1]

    def _phase(self, key):
        (self.theta, self.hat, self.radius, self.bits,
         sent_i, qlev_i, hat_i, r_i, b_i) = self.fns["phase"](
            self.theta, self.hat, self.lam, self.radius, self.bits,
            self.active, key, jnp.asarray(self.rnd, jnp.int32), self.i)
        if not bool(sent_i):
            return False, {}, self.flag_bits
        # The wire carries (qlev, R, b) — that is what payload_bits prices
        # — and the receiver's dequantize_rows(qlev, hat_prev, R, b) is the
        # same arithmetic that committed hat_i on the sender.  The message
        # also transports the committed row itself: recomputing it in a
        # separately jitted program is NOT guaranteed bit-stable (XLA may
        # FMA-contract a*b+c differently per compilation), and the
        # keystone contract locks the sim to the lockstep reference
        # bit-for-bit.  tests/test_sim.py checks the codec roundtrip
        # against the shipped row.
        body = {"hat": hat_i, "qlev": qlev_i, "radius": r_i, "bits": b_i} \
            if self.cfg.quantize else {"hat": hat_i}
        return True, body, self.payload_bits

    def _apply(self, j, msg):
        self.hat = self.fns["apply"](self.hat, j, msg.body["hat"])

    def _post_advance(self, j, rnd):
        if self.staleness > 0:
            self._nbr_hist[j][rnd] = jax.tree.map(lambda a: a[j], self.hat)

    def _skip_hook(self):
        # absent round: own hat unchanged — record it so the round-(k-S)
        # common-round dual can look the lag snapshot up later
        if self.staleness > 0:
            self._own_hist[self.rnd] = jax.tree.map(lambda a: a[self.i],
                                                    self.hat)

    def _edge_mask(self) -> np.ndarray:
        """1.0 on live incident edges whose neighbor hat is round-fresh.

        Barriered (staleness 0) completion implies nbr_round[j] == rnd, so
        the mask is all-ones there (bit-parity preserved; x*1.0 is exact)
        and only drop-frozen edges are gated off.  An edge whose far
        endpoint sits this round out (partial participation / pre-join) is
        also frozen: the dual updates only when BOTH endpoints participate,
        so the two mirrors integrate identical increments."""
        mask = self.edge_alive.copy()
        for j, e in self._edge_of.items():
            if j not in self.dead and self.nbr_round[j] < self.rnd:
                mask[e] = 0.0
            if not self._participates(self.rnd, j):
                mask[e] = 0.0
        return mask

    def _dual_update(self):
        if self.staleness == 0:
            self.lam = self.fns["dual"](self.lam, self.hat,
                                        jnp.asarray(self._edge_mask()))
            return
        # async: dual step on the round-(k-S) common snapshot of both
        # endpoints (module docstring), gated off during the S fill rounds
        self._own_hist[self.rnd] = jax.tree.map(lambda a: a[self.i],
                                                self.hat)
        lag = self.rnd - self.staleness
        if lag >= 0:
            hat_sub = _set_row(self.hat, self.i, self._own_hist[lag])
            mask = self.edge_alive.copy()
            for j, e in self._edge_of.items():
                row = self._nbr_hist[j].get(lag)
                if row is None:        # dead before round `lag` — frozen
                    mask[e] = 0.0
                else:
                    hat_sub = _set_row(hat_sub, j, row)
                if not self._participates(self.rnd, j):
                    mask[e] = 0.0      # both-endpoints participation rule
            self.lam = self.fns["dual"](self.lam, hat_sub,
                                        jnp.asarray(mask))
        for h in (self._own_hist, *self._nbr_hist.values()):
            for r in [r for r in h if r < self.rnd - self.staleness]:
                del h[r]

    def _peer_down_hook(self, j):
        e = self._edge_of.get(j)
        if e is not None:
            self.edge_alive[e] = 0.0

    def _snapshot(self):
        lam_rows = {self._edge_of[j]: np.asarray(self.lam[self._edge_of[j]])
                    for j in self.neighbors
                    if int(self.topo.edges[self._edge_of[j], 0]) == self.i}
        return dict(theta=np.asarray(self.theta[self.i]),
                    hat=np.asarray(self.hat[self.i]),
                    radius=np.asarray(self.radius[self.i]),
                    bits=np.asarray(self.bits[self.i]),
                    sent=self.sent_log[-1], lam_rows=lam_rows)


class TrainerActor(BaseActor):
    """Actor replaying QGADMMTrainer's unsharded reference step pieces.

    The local view is the trainer's full stacked 9-tuple state; `fns`
    (sim.runner._trainer_fns) wraps the trainer's phase_compute /
    phase_apply / dual_update methods, jitted once for all actors.
    """

    def __init__(self, i, topo, *, st0, batch, fns, keys, trainer,
                 payload_bits, flag_bits, **kw):
        super().__init__(i, topo, **kw)
        self.st = st0
        self.batch = batch
        self.fns = fns
        self.keys = keys
        self.trainer = trainer
        self.payload_bits = float(payload_bits)
        self.flag_bits = float(flag_bits)
        self.quantize = trainer.dcfg.gadmm.quantize
        self.active = jnp.asarray(topo.head_mask if self.is_head
                                  else ~topo.head_mask)
        # neighbor j -> the directed edge (j, i), whose dual-mask entry
        # gates i's slot for j, and that slot's row i * C + color in the
        # trainer's owner-indexed state: what i knows about j
        self.eidx = trainer.eidx
        self._in_edge = self.eidx.in_edges(self.i)
        self._slot = {j: self.i * topo.num_ports + int(self.eidx.color[d])
                      for j, d in self._in_edge.items()}
        self.edge_alive = np.ones((self.eidx.num_directed,), np.float32)
        # S-deep lag histories for the async common-round dual (module
        # docstring): round -> committed own hat row / reconstructed slot
        self._own_hist: dict[int, Any] = {}
        self._nbr_hist: dict[int, dict[int, Any]] = \
            {j: {} for j in self.neighbors}

    def _phase_key(self):
        return self.keys[self.rnd][0 if self.is_head else 1]

    def _phase(self, key):
        self.st, sent_i, hat_row, wire_i, r_i, b_i = self.fns["phase"](
            self.st, self.batch, self.active, key,
            jnp.asarray(self.rnd, jnp.int32), self.i)
        if not bool(sent_i):
            return False, {}, self.flag_bits
        # wire_i/(R, b) are the billed wire content; hat_row is the
        # committed reconstruction the receivers store (see GraphActor:
        # cross-program recompute is not FMA-stable, and the trainer's
        # in-program receiver path is bit-identical to the sender's commit
        # — checked by the sim-vs-trainer parity suite).
        body = {"hat": hat_row, "wire": wire_i}
        if self.quantize:
            body["radius"] = r_i
            body["bits"] = b_i
        return True, body, self.payload_bits

    def _apply(self, j, msg):
        self.st = self.fns["apply"](self.st, self._slot[j], msg.body["hat"])

    def _post_advance(self, j, rnd):
        if self.staleness > 0:
            s = self._slot[j]
            self._nbr_hist[j][rnd] = jax.tree.map(lambda a: a[s],
                                                  self.st[2])

    def _dual_update(self):
        mask = self.edge_alive.copy()
        if self.staleness == 0:
            # same fresh-edge gating as GraphActor._edge_mask, on the
            # directed edges with dst=i (the other slots of the local
            # view are don't-care)
            for j, d in self._in_edge.items():
                if j not in self.dead and self.nbr_round[j] < self.rnd:
                    mask[d] = 0.0
            self.st = self.fns["dual"](self.st, jnp.asarray(mask))
            return
        # async: splice the round-(k-S) common snapshot (own hat row +
        # in-edge slots) into a scratch state, take the dual step there,
        # and keep only its lam_nbr — this is the trainer pipeline's
        # `hat_lag` dual rule (dist.qgadmm._stale_round)
        (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t) = self.st
        self._own_hist[self.rnd] = jax.tree.map(lambda a: a[self.i], hat)
        lag = self.rnd - self.staleness
        if lag >= 0:
            hat_sub = _set_row(hat, self.i, self._own_hist[lag])
            hat_nbr_sub = hat_nbr
            for j, d in self._in_edge.items():
                row = self._nbr_hist[j].get(lag)
                if row is None:        # dead before round `lag` — frozen
                    mask[d] = 0.0
                else:
                    hat_nbr_sub = _set_row(hat_nbr_sub, self._slot[j], row)
            st_sub = (theta, hat_sub, hat_nbr_sub, lam_nbr, radius,
                      bits, mu, nu, t)
            lam_nbr = self.fns["dual"](st_sub, jnp.asarray(mask))[3]
            self.st = (theta, hat, hat_nbr, lam_nbr, radius, bits,
                       mu, nu, t)
        for h in (self._own_hist, *self._nbr_hist.values()):
            for r in [r for r in h if r < self.rnd - self.staleness]:
                del h[r]

    def _peer_down_hook(self, j):
        self.edge_alive = self.edge_alive.copy()
        self.edge_alive[self._in_edge[j]] = 0.0

    def _snapshot(self):
        import jax
        (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t) = self.st
        row = lambda tree: jax.tree.map(
            lambda a: np.asarray(a[self.i]), tree)
        # slot (i, c): zeros where i has no color-c port
        ports = self.topo.num_ports
        port_row = lambda slots, c: jax.tree.map(
            lambda a: np.asarray(a[self.i * ports + c]), slots)

        return dict(theta=row(theta), hat=row(hat),
                    hat_nbr=tuple(port_row(hat_nbr, c)
                                  for c in range(ports)),
                    lam_nbr=tuple(port_row(lam_nbr, c)
                                  for c in range(ports)),
                    radius=np.asarray(radius[self.i]),
                    bits=np.asarray(bits[self.i]),
                    sent=self.sent_log[-1])
