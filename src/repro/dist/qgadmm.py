"""Sharded multi-worker Q-GADMM trainer (paper Algorithm 1, eqs. 14-18).

Workers live on the 'worker' axis of a factored ('worker', 'fsdp', 'model')
mesh (repro.launch.mesh.factor_mesh); each worker's replica of the model is
FSDP+TP sharded inside its device group.  One train step is the Q-SGADMM
iteration (paper Sec. IV / V-B):

  * heads (chain positions 0, 2, ...) run `local_iters` Adam steps on the
    stochastic augmented Lagrangian of eq. 14 (their own data shard plus dual
    and proximal terms to the *reconstructed* neighbor models),
  * heads quantize theta - theta_hat_prev and transmit (q, R, b),
  * tails (positions 1, 3, ...) do the same against the heads' fresh hats,
  * every worker applies the damped dual update of eq. 18
    (lam += alpha * rho * (hat_n - hat_{n+1})).

The quantized exchange is FUSED onto one flat wire buffer per worker: all
parameter leaves are flattened into a single (W, D_pad) row per worker, and
one fused quantize->pack->ppermute->unpack->dequantize pipeline replaces
the L small per-leaf ops.  In the sharded step both the codec and the
nibble packing run INSIDE shard_map — every device quantizes and packs
exactly the wire slab it owns (the production TPU layout, and it keeps the
codec's pad/reshape/slice internals away from the SPMD partitioner, which
XLA:CPU miscompiles; see the RoPE note in dist.sharding).
`DistConfig.wire_impl` selects the codec implementation — 'jnp' (pure-jnp
reference), 'pallas' (Pallas kernels from repro.kernels.{quantize,pack} in
interpret mode, CPU only), or 'pallas_compiled' (compiled Pallas, TPU;
repro.launch.train selects it whenever the backend is a TPU).
All three consume one shared uniform draw over the wire buffer, so they
are bit-identical in q; every sender takes its new hat from the receivers'
own decode of q (`_decode`).  per_tensor radius mode expands its per-leaf
radii into per-element values with a segment-scalar gather before the codec.
When the effective bit width is <= 4 each device nibble-packs its shard
(kernels/pack wire format, `packed_len` bytes per shard) right before the
jax.lax.ppermute, halving the bytes on the interconnect; `pack_wire=None`
(the default) enables this automatically.

Both endpoints of every edge reconstruct the transmitted model with the same
flat-buffer arithmetic from their own synchronized copy of the sender's
previous hat, so sender and receiver stay bit-identical — the algorithm's
key invariant.

`overlap=True` double-buffers the gauss-seidel exchange: the heads' payload
is put on the wire and the tails run their local Adam iterations against the
*previous* neighbor hats while it is in flight (one-exchange staleness,
beyond-paper), letting XLA hide the chain latency behind compute.

`mode="jacobi"` collapses the two masked phases into one simultaneous update
of all workers (benchmarks/bench_jacobi.py measures the trade-off), and
`num_workers=1` degenerates to plain FSDP data-parallel Adam with no chain
collectives at all.

Beyond the paper's chain, `DistConfig.topology` runs the same two-phase
sweep on any connected bipartite worker graph (core.topology: 'ring',
'star', '2d-torus', or an explicit Topology).  A proper edge coloring
(Koenig) splits the edges into matchings, and each matching is exactly
one jax.lax.ppermute permutation — the collective schedule is the
canonical core.topology.edge_schedule, derived from the graph, never
hard-coded +-1 shifts.

State layout.  Neighbor state is OWNER-INDEXED: `DistState.hat_nbr` and
`lam_nbr` leaves are (W * C, ...) with C = the topology's number of edge
colors, slot (w, c) at row w * C + c, sharded like theta ('worker' on dim
0, so each worker's C rows sit on its own devices).  Slot (w, c) is what
worker w knows about its color-c partner's hat and w's mirror of that
edge's dual (canonical head -> tail orientation; both endpoints hold
bitwise-equal mirrors in lockstep); slots of absent ports hold zeros and
are masked.  Each chip therefore holds, decodes and dual-updates only
its own workers' slots: slot (w, c) is fed by recv[c][w], exactly what
the color-c ppermute delivers to w, so the decode and the dual update
are elementwise over worker-sharded operands.  `DistState.hat_edge` /
`lam_edge` read the same state as the topology's 2E directed rows
(core.topology.edge_index order, row d = slot (dst(d), color(d))).

`DistConfig.staleness = S > 0` replaces the per-color exchange barrier
with an explicit send / recv-start / recv-done pipeline: each round's
merged head+tail payload is SENT into an S-deep in-flight ring buffer
(`DistState.inbox` — recv-start), and the round-(k-S) entry is moved by
the per-color exchange and decoded into the slots at the top of round k
(recv-done), so every worker
computes against neighbor hats that are exactly S rounds stale.  Duals
update against the matching S-stale snapshot of the worker's OWN hat
(`DistState.hat_lag`, decoded from the same payload stream), so both
endpoints of an edge keep pairing the same (head, tail) hat rounds and
the dual mirrors stay synchronized — the trainer-side analog of
sim.worker's fresh-edge dual gating, with the first S pipeline-fill
rounds gated off.  Wire accounting bills a payload on the round it is
sent, never the round it is consumed.  S=0 is the barriered schedule,
bitwise-identical to the pre-refactor port-dense trainer
(tests/test_wire_path.py replays committed goldens to pin this).

`DistConfig.censor` adds CQ-GGADMM censored transmissions (core.censor): a
worker whose freshly quantized model moved less than tau*xi^k in L2 keeps
silent for the round — the wire carries only a 1-bit censor flag, every
receiver (and the sender itself) reuses the previous hat, and because the
skip decision is computed from quantized values both ends already share,
the sender==receiver bit-sync invariant survives.  `wire_bits_per_round`
then becomes data-dependent: skipped links are billed FLAG_BITS instead of
the payload row, and the step reports a `skip_rate` metric.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import censor as censor_mod
from repro.core.censor import CensorConfig
from repro.core.gadmm import GADMMConfig
from repro.core.quantizer import (LayerwiseConfig, _next_bits, allocate_bits,
                                  header_bits, levels_of)
from repro.core.topology import (Topology, build_topology, edge_index,
                                 edge_schedule)
from repro.kernels import check_interpret
from repro.kernels.pack import ops as pack_ops
from repro.kernels.pack.ref import packed_len
from repro.kernels.quantize import quantize as q_kernel
from repro.kernels.quantize import ref as q_ref

from . import sharding as sh

Array = jax.Array

_ADAM_B1, _ADAM_B2, _ADAM_EPS = 0.9, 0.999, 1e-8

# The layers of one round.  Each wraps its work in the named scope
# "qgadmm.<layer>", which the compiled step keeps in every instruction's
# metadata (op_name), so a device trace can be split by layer.  The scopes
# never nest: every op of a round falls under at most one of them.
LAYERS = ("local_solve", "codec", "exchange", "decode", "dual", "metrics")


def _layer(name: str):
    """The named scope of one of LAYERS (a context manager and a
    decorator)."""
    if name not in LAYERS:
        raise ValueError(f"unknown layer {name!r}")
    return jax.named_scope(f"qgadmm.{name}")


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Static configuration of the distributed Q-GADMM trainer.

    num_workers: GADMM chain length == size of the mesh 'worker' axis.
    gadmm:       rho / quantizer / dual-damping configuration (shared with the
                 single-host reference implementations in repro.core).
    local_iters: Adam steps per worker per phase (paper Sec. IV, Q-SGADMM).
    local_lr:    local Adam learning rate.
    mode:        'gauss-seidel' (paper: masked head/tail phases) or 'jacobi'
                 (one simultaneous phase; half the per-step compute).
    microbatches:gradient accumulation inside each local step.
    radius_mode: 'global' = one R per worker per round (paper-faithful);
                 'per_tensor' = one R per parameter tensor (tighter ranges,
                 beyond-paper; costs 32 bits/tensor of header).
    state_dtype: cast chain state (theta/hat/duals) to this dtype (e.g.
                 bf16); None keeps the model's param dtype.
    uneven_shard:allow GSPMD-padded uneven sharding of parameter dims.
    pack_wire:   nibble-pack the uint8 wire when bits <= 4 (halves bytes).
                 None (default) = auto: packed whenever the effective bit
                 width (max_bits if adaptive, else bits) is <= 4.
    seq_shard:   additionally shard the batch sequence dim over 'model'.
    wire_impl:   codec for the fused quantize/pack wire path — 'jnp'
                 (pure-jnp reference), 'pallas' (kernels in interpret mode,
                 CPU only: refused on a TPU backend), 'pallas_compiled'
                 (compiled Pallas, TPU).  All three are bit-identical
                 (shared uniform-draw convention).
    overlap:     double-buffer the gauss-seidel exchange: tails run their
                 local iterations against the previous neighbor hats while
                 the heads' payload is in flight (one-exchange staleness).
    topology:    worker graph — 'chain' (paper), 'ring', 'star', 'torus2d',
                 or an explicit core.topology.Topology (any connected
                 bipartite graph).  Determines the phases' head/tail split
                 and the ppermute schedule (one permutation per edge color).
    censor:      optional core.censor.CensorConfig: transmit a phase's
                 quantized delta only when ||hat_new - hat_prev||_2 >
                 tau*xi^k; skipped links cost 1 flag bit on the wire.
    staleness:   S = 0 (default): barriered per-color exchange, every
                 round consumes this round's payloads.  S > 0: pipelined
                 send/recv-start/recv-done exchange — payloads spend S
                 rounds in flight (DistState.inbox) and every worker
                 computes against neighbor hats exactly S rounds old,
                 duals fresh-edge-gated onto matching S-stale snapshots
                 (the trainer promotion of repro.sim's bounded-staleness
                 async schedule; see the module docstring).
    participation: per-round Bernoulli rate of each worker taking part
                 (1.0 = everyone, the default — that path is bitwise
                 identical to the pre-participation trainer).  Each round
                 draws a (W,) mask by folding a constant into the round
                 key (every worker derives the same mask — shared setup
                 knowledge, no extra wire traffic).  An absent worker
                 skips its local iterations and transmits nothing; its
                 neighbors drop the frozen hat from their neighbor sums
                 with degree-renormalized weights (deg / #participating
                 neighbors — exactly 1.0 whenever everyone is present,
                 so fully-present rounds are unbiased AND bit-stable),
                 and an edge's dual updates only when BOTH endpoints
                 participate, keeping the lam mirrors synchronized.
                 Composes with censoring (absent != censored: a censored
                 worker computed but stayed silent) and with the
                 staleness pipeline (the mask gates the round's compute
                 and its in-flight payload alike).
    layerwise:   optional core.quantizer.LayerwiseConfig (L-FGADMM,
                 arXiv:1911.03654): each pytree leaf gets its own bit
                 width, exchange period and censor threshold, with an
                 optional per-round bit-budget controller
                 (quantizer.allocate_bits) reallocating a fixed payload
                 budget toward the leaves whose residuals moved most.
                 Forces radius_mode='per_tensor' (per-leaf radii are the
                 layerwise codec's native sideband) and requires the
                 quantized wire.  An unsent leaf rides the payload with
                 radius 0 — the codec's R == 0 guard makes it a no-op on
                 both endpoints, so receivers hold the leaf's last hat and
                 the sender==receiver bit-sync invariant survives.
                 Composes with censor (worker-level threshold on the
                 leaf-masked candidate commit), staleness (the masked
                 radius rides the inbox ring) and participation.
    telemetry:   extend the step metrics with the observability counters
                 (repro.obs): billed wire bits split into payload/header/
                 flags, per-worker transmit mask and directed-link
                 counts, dual-residual norm, participation popcount,
                 per-leaf bit allocation under layerwise.  All of them
                 are pure functions of values the step already computes —
                 the state stream is bitwise-identical either way; False
                 keeps the original minimal metrics dict.
    check_invariants: run the repro.obs.checks live invariants on this
                 trainer's drained metric windows (the launch CLIs also
                 honor env REPRO_CHECK=1).
    """

    num_workers: int
    gadmm: GADMMConfig
    local_iters: int = 1
    local_lr: float = 1e-3
    mode: str = "gauss-seidel"
    microbatches: int = 1
    radius_mode: str = "global"
    state_dtype: Any = None
    uneven_shard: bool = False
    pack_wire: bool | None = None
    seq_shard: bool = False
    wire_impl: str = "jnp"
    overlap: bool = False
    topology: Any = "chain"
    censor: CensorConfig | None = None
    staleness: int = 0
    participation: float = 1.0
    layerwise: LayerwiseConfig | None = None
    telemetry: bool = True
    check_invariants: bool = False

    def __post_init__(self):
        assert 0.0 < self.participation <= 1.0, self.participation
        assert self.mode in ("gauss-seidel", "jacobi"), self.mode
        assert self.radius_mode in ("global", "per_tensor"), self.radius_mode
        if self.layerwise is not None:
            assert self.gadmm.quantize, \
                "layerwise bit allocation needs the quantized wire"
            object.__setattr__(self, "radius_mode", "per_tensor")
        build_topology(self.topology, self.num_workers)  # validate early
        assert self.wire_impl in ("jnp", "pallas", "pallas_compiled"), \
            self.wire_impl
        check_interpret(self.wire_impl == "pallas")
        assert not (self.overlap and self.mode != "gauss-seidel"), \
            "overlap (double-buffered exchange) only applies to the " \
            "two-phase gauss-seidel mode"
        assert self.staleness >= 0, self.staleness
        assert self.staleness == 0 or (self.mode == "gauss-seidel"
                                       and not self.overlap), \
            "staleness > 0 pipelines the two-phase gauss-seidel exchange " \
            "(jacobi and overlap have their own schedules)"
        # The chain wire is always dense; top-k sparsification only exists in
        # the single-host reference (gadmm._quantize_rows) so far.
        assert self.gadmm.topk_frac >= 1.0, \
            "topk sparsification is not supported by the distributed trainer"
        q = self.gadmm.qcfg
        max_b = q.max_bits if q.adapt_bits else q.bits
        lw = self.layerwise
        if lw is not None:
            # effective max bit width across leaves: the dense simulated
            # exchange packs the WHOLE row, so all leaves must fit a nibble
            if lw.adapt_bits or lw.budget_bits is not None:
                max_b = lw.max_bits
            elif lw.bits is None:
                max_b = q.bits
            elif isinstance(lw.bits, int):
                max_b = lw.bits
            else:
                max_b = max(int(b) for b in lw.bits)
        if self.pack_wire is None:
            object.__setattr__(
                self, "pack_wire", bool(self.gadmm.quantize and max_b <= 4))
        if self.pack_wire and self.gadmm.quantize:
            assert max_b <= 4, "pack_wire needs <= 4-bit levels"


class DistState(NamedTuple):
    """Replicated-per-worker chain state; parameter-shaped pytree leaves are
    stacked with a leading (num_workers,) dim sharded over the mesh
    'worker' axis.

    Neighbor state is OWNER-INDEXED: ``hat_nbr``/``lam_nbr`` leaves are
    (W * C, ...), C = the topology's edge colors (core.topology
    ``num_ports``), slot (w, c) at row w * C + c, sharded like ``theta``
    (the W*C rows in W blocks of C).  Slot (w, c) holds what w
    knows about its color-c partner's hat and w's mirror of the shared
    edge dual (canonical head -> tail orientation — in lockstep both
    endpoints are bitwise-equal); absent ports hold zeros.  A worker's
    slots live on its own chip, next to its theta.

    Cost: the layout holds W * C rows where the directed edges number 2E
    = sum of degrees.  A chain or ring (C = 2) holds 2W against 2(W-1) or
    2W; a star (hub degree W-1, so C = W-1) holds W(W-1) against 2(W-1),
    every leaf's slots padded to the hub's degree.

    ``hat_edge``/``lam_edge`` read the slots as the 2E directed-edge rows
    in core.topology.edge_index order (sorted by (dst, src)): row d is
    slot (dst(d), color(d)), whose row ``edge_slot[d]`` = dst(d) * C +
    color(d) the state carries, so a reader needs no trainer.

    The slot dim is merged into the leading dim, not a dim of its own:
    with C = 1 (a W=2 chain) the leaves are (W, ...) like theta's, and
    the one-chip program keeps theta's layouts (a separate unit dim cost
    XLA:TPU 93 more layout copies a round at whisper-tiny).

    ``inbox``/``hat_lag`` exist only at staleness S > 0: the S-deep ring of
    in-flight payload rounds ({wire, radius, bits, sent} stacked with a
    leading (S,) dim) and the worker's own hat delayed S rounds (decoded
    from the same payload stream the neighbors decode — the consistent
    snapshot the dual update pairs against)."""

    theta: Any      # current primal parameters
    theta_hat: Any  # own last-quantized model (== what neighbors hold)
    hat_nbr: Any    # owner slots (W * C, ...): w's copy of its partner's hat
    lam_nbr: Any    # owner slots (W * C, ...): w's mirror of the edge dual
    radius: Array   # (W,) global mode | (W, n_tensors) per_tensor mode
    bits: Array     # (W,) int32 | (W, n_tensors) layerwise mode
    opt_mu: Any     # local Adam first moment
    opt_nu: Any     # local Adam second moment
    opt_t: Array    # (W,) int32 Adam step counts
    key: Array      # PRNG key (stochastic rounding)
    step: Array     # () int32
    edge_slot: Array  # (2E,) int32: slot row dst * C + color of each edge
    inbox: Any = () # staleness > 0: S-deep in-flight payload ring
    hat_lag: Any = ()  # staleness > 0: own hat, S rounds delayed

    @property
    def hat_edge(self):
        """(2E, ...) directed-edge rows: dst(d)'s copy of src(d)'s hat."""
        return _edge_rows(self.hat_nbr, self.edge_slot)

    @property
    def lam_edge(self):
        """(2E, ...) directed-edge rows: dst(d)'s mirror of the edge dual."""
        return _edge_rows(self.lam_nbr, self.edge_slot)


def init_state(init_fn: Callable[[Array], Any], key: Array,
               dcfg: DistConfig) -> DistState:
    """State at k=0: every worker starts from the same init, hats at zero
    (the paper initializes theta_hat^0 = 0)."""
    w = dcfg.num_workers
    topo = build_topology(dcfg.topology, w)
    k_init, k_state = jax.random.split(key)
    params = init_fn(k_init)
    if dcfg.state_dtype is not None:
        params = jax.tree.map(
            lambda a: a.astype(dcfg.state_dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    theta = jax.tree.map(
        lambda a: jnp.tile(a[None], (w,) + (1,) * a.ndim), params)
    zeros = lambda: jax.tree.map(jnp.zeros_like, theta)
    n_tensors = len(jax.tree.leaves(theta))
    radius = (jnp.zeros((w,), jnp.float32) if dcfg.radius_mode == "global"
              else jnp.zeros((w, n_tensors), jnp.float32))
    if dcfg.layerwise is not None:
        sizes = [int(np.prod(l.shape)) for l in jax.tree.leaves(params)]
        lw_bits, _, _ = dcfg.layerwise.resolve(sizes, dcfg.gadmm.qcfg.bits)
        bits0 = jnp.tile(jnp.asarray(lw_bits, jnp.int32)[None], (w, 1))
    else:
        bits0 = jnp.full((w,), dcfg.gadmm.qcfg.bits, jnp.int32)
    c = topo.num_ports
    slot_zeros = lambda: jax.tree.map(
        lambda a: jnp.zeros((w * c,) + a.shape, a.dtype), params)
    eidx = edge_index(topo)
    inbox, hat_lag = (), ()
    if dcfg.staleness > 0:
        s = dcfg.staleness
        d = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        wire_dtype = jnp.uint8 if dcfg.gadmm.quantize else jnp.float32
        inbox = {
            "wire": jnp.zeros((s, w, d), wire_dtype),
            "radius": jnp.zeros((s,) + radius.shape, jnp.float32),
            "bits": jnp.zeros((s,) + bits0.shape, jnp.int32),
            # all-False sent flags = the pipeline-fill rounds decode to
            # no-ops, exactly like S censored rounds
            "sent": jnp.zeros((s, w), bool),
        }
        hat_lag = zeros()
    return DistState(
        theta=theta, theta_hat=zeros(),
        hat_nbr=slot_zeros(), lam_nbr=slot_zeros(),
        radius=radius, bits=bits0,
        opt_mu=zeros(), opt_nu=zeros(),
        opt_t=jnp.zeros((w,), jnp.int32),
        key=k_state, step=jnp.zeros((), jnp.int32),
        edge_slot=jnp.asarray(eidx.dst * c + eidx.color, jnp.int32),
        inbox=inbox, hat_lag=hat_lag)


# ------------------------------------------------------------- tree utils ---
def _bmask(m: Array, leaf: Array) -> Array:
    return m.reshape(m.shape + (1,) * (leaf.ndim - m.ndim))


def _rows(x: Array, idx) -> Array:
    """x[idx] along axis 0 for a static index vector; -1 selects a zero row.
    Built from static slices, not a gather — XLA:TPU takes minutes to
    compile a gather whose slices are whole rows of a wide wire buffer."""
    idx = np.asarray(idx)
    parts = [jnp.zeros_like(x[:1]) if i < 0 else x[i:i + 1]
             for i in idx.tolist()]
    if not parts:
        return x[:0]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


@jax.jit
def _take_rows(slots, edge_slot):
    return jax.tree.map(lambda a: jnp.take(a, edge_slot, axis=0), slots)


def _edge_rows(slots, edge_slot):
    """Owner slots (W * C, ...) -> the (2E, ...) directed-edge rows, row d
    from slot edge_slot[d]: one gather a leaf, or the slots themselves (no
    copy) where every slot is an edge row in edge order (a W=2 chain).  A
    gather, not static slices: for a worker-sharded state XLA:TPU compiles
    it in seconds (whisper-tiny on v5e:2x2: 5 s against 18 s, and 54 MB of
    temporaries against 3.0 GB a chip)."""
    if not isinstance(edge_slot, jax.core.Tracer):
        idx = np.asarray(edge_slot)
        if ({a.shape[0] for a in jax.tree.leaves(slots)} == {idx.size}
                and (idx == np.arange(idx.size)).all()):
            return slots
    return _take_rows(slots, edge_slot)


def _put_rows(x: Array, idx, vals: Array) -> Array:
    """x with its rows idx (static, ascending) replaced by vals: the inverse
    of _rows(x, idx), one static-offset update per run of consecutive
    rows (no scatter)."""
    idx = np.asarray(idx)
    runs = np.split(np.arange(idx.size), np.flatnonzero(np.diff(idx) != 1) + 1)
    for r in runs:
        if r.size:
            x = jax.lax.dynamic_update_slice_in_dim(
                x, vals[r[0]:r[-1] + 1], int(idx[r[0]]), axis=0)
    return x


def _decode(q: Array, hat_f: Array, radius: Array, levels: Array) -> Array:
    """The dequantize arithmetic of the wire (f32): levels q against the
    previous hat, R == 0 a no-op.  The one definition shared by the sender
    (its committed hat) and every receiver (its copy), so both ends of an
    edge hold bitwise-equal hats by construction."""
    safe_r = jnp.maximum(radius, 1e-30)
    step = 2.0 * safe_r / levels
    out = hat_f + step * q.astype(jnp.float32) - radius
    return jnp.where(radius > 0, out, hat_f)


def _twhere(m: Array, a, b):
    return jax.tree.map(lambda x, y: jnp.where(_bmask(m, x), x, y), a, b)


def _tvdot(a, b) -> Array:
    parts = jax.tree.leaves(jax.tree.map(
        lambda x, y: jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32)),
        a, b))
    return sum(parts) if parts else jnp.zeros(())


def _tsqnorm(a, b) -> Array:
    parts = jax.tree.leaves(jax.tree.map(
        lambda x, y: jnp.sum(
            (x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2), a, b))
    return sum(parts) if parts else jnp.zeros(())


def _leaf_sizes(leaves) -> list[int]:
    """Flat per-worker size of each stacked (W, ...) leaf."""
    return [int(np.prod(l.shape[1:])) for l in leaves]


class QGADMMTrainer:
    """Decentralized trainer for one model over the factored worker mesh.

    model: a repro.models module (init / loss_fn(params, batch, cfg)).
    cfg:   its ArchConfig.
    dcfg:  DistConfig above.
    worker_mesh: ('worker', 'fsdp', 'model') mesh from factor_mesh.
    """

    def __init__(self, model, cfg, dcfg: DistConfig, worker_mesh: Mesh):
        self.model = model
        self.cfg = cfg
        self.dcfg = dcfg
        self.mesh = worker_mesh
        self.topo: Topology = build_topology(dcfg.topology, dcfg.num_workers)
        pmask_np = self.topo.port >= 0                   # (W, C) static
        self.pmask = jnp.asarray(pmask_np, jnp.float32)
        self.is_head = jnp.asarray(self.topo.head_mask)
        self.sign = jnp.where(self.is_head, 1.0, -1.0).astype(jnp.float32)
        # Directed-edge tables (the simulator's and the readers' edge
        # order) and each owner slot's dual sign: +1 a head's present
        # port, -1 a tail's, 0 an absent port.
        self.eidx = edge_index(self.topo)
        self._d_src = jnp.asarray(self.eidx.src, jnp.int32)    # (2E,)
        self._d_dst = jnp.asarray(self.eidx.dst, jnp.int32)    # (2E,)
        self._slot_sign = (self.sign[:, None] * self.pmask).reshape(-1)
        # layerwise: per-leaf tables cache + the per-leaf eq. 11 config
        self._lw_cache: dict = {}
        lw = dcfg.layerwise
        self._lw_qcfg = (dataclasses.replace(
            dcfg.gadmm.qcfg, adapt_bits=True, max_bits=lw.max_bits,
            bits=min(dcfg.gadmm.qcfg.bits, lw.max_bits))
            if lw is not None and lw.adapt_bits else None)

    def _lw_tables(self, sizes: tuple):
        """Resolved per-leaf (bits, periods, taus) device tables for a flat
        leaf-size tuple (static; cached per distinct pytree shape)."""
        if sizes not in self._lw_cache:
            bits, periods, taus = self.dcfg.layerwise.resolve(
                list(sizes), self.dcfg.gadmm.qcfg.bits)
            self._lw_cache[sizes] = (
                jnp.asarray(bits, jnp.int32),
                jnp.asarray(periods, jnp.int32),
                None if taus is None else jnp.asarray(taus, jnp.float32))
        return self._lw_cache[sizes]

    def _edge_to_slots(self, x: Array) -> Array:
        """(2E,) per-directed-edge values -> (W * C,) owner slots (0 where
        a port is absent)."""
        return _rows(x, self.eidx.slot.reshape(-1))

    def _per_slot(self, tree):
        """(W, ...) per-worker rows -> (W * C, ...): row w repeated for
        each of its C slots (local to the worker's chip)."""
        w, c = self.dcfg.num_workers, self.topo.num_ports
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[:, None], (w, c) + a.shape[1:])
            .reshape((w * c,) + a.shape[1:]), tree)

    # ------------------------------------------------------------ views ----
    def _port_view(self, slots, rows=None):
        """Owner slots (W * C, ...) -> tuple over edge colors of stacked
        (W, ...) trees, the per-port form the local loss is written
        against: a reshape to (W, C, ...) and a static slice along the
        slot dim, local to each worker's chip.  rows: a static worker
        subset; the view then has one row each."""
        w, ports = self.dcfg.num_workers, self.topo.num_ports

        def view(a, c):
            v = a.reshape((w, ports) + a.shape[1:])[:, c]
            return v if rows is None else _rows(v, rows)

        return tuple(jax.tree.map(lambda a: view(a, c), slots)
                     for c in range(ports))

    def port_views(self, state: DistState) -> dict:
        """The neighbor state as per-(worker, color) port views, tuples over
        colors of (W, ...) trees — the layout-independent surface the
        golden replay tier and the sim parity tests compare."""
        return {"hat_nbr": self._port_view(state.hat_nbr),
                "lam_nbr": self._port_view(state.lam_nbr)}

    # ------------------------------------------------------------ specs ----
    def batch_specs(self, batch):
        seq_axes = ("model",) if self.dcfg.seq_shard else None

        def leaf(a):
            rules = [(0, ("worker",)), (1, ("fsdp",))]
            if seq_axes and a.ndim >= 3:
                rules.append((2, seq_axes))
            return sh._assign(a.shape, rules, self.mesh)

        return jax.tree.map(leaf, batch)

    def state_specs(self, state: DistState) -> DistState:
        au = self.dcfg.uneven_shard
        pspec = functools.partial(sh.tree_specs, leaf_rule=sh.leaf_train_spec,
                                  mesh=self.mesh, allow_uneven=au)
        wspec = P("worker") if self.dcfg.num_workers > 1 else P(None)
        inbox, hat_lag = (), ()
        if self.dcfg.staleness > 0:
            inbox = {
                "wire": P(None, *wspec, None),
                "radius": (P(None, *wspec) if state.inbox["radius"].ndim == 2
                           else P(None, *wspec, None)),
                "bits": (P(None, *wspec) if state.inbox["bits"].ndim == 2
                         else P(None, *wspec, None)),
                "sent": P(None, *wspec),
            }
            hat_lag = pspec(state.hat_lag)
        return DistState(
            theta=pspec(state.theta), theta_hat=pspec(state.theta_hat),
            # owner slots: W blocks of C rows, each on its worker's devices
            hat_nbr=pspec(state.hat_nbr), lam_nbr=pspec(state.lam_nbr),
            radius=(wspec if state.radius.ndim == 1
                    else P(*wspec, None)),
            bits=(wspec if state.bits.ndim == 1 else P(*wspec, None)),
            opt_mu=pspec(state.opt_mu), opt_nu=pspec(state.opt_nu),
            opt_t=wspec, key=P(None), step=P(), edge_slot=P(None),
            inbox=inbox, hat_lag=hat_lag)

    def _shardings(self, specs):
        return sh.tree_shardings(specs, self.mesh)

    def place(self, state: DistState, batch):
        """device_put state + batch onto the worker mesh."""
        state = jax.device_put(state, self._shardings(self.state_specs(state)))
        batch = jax.tree.map(jnp.asarray, batch)
        batch = jax.device_put(batch, self._shardings(self.batch_specs(batch)))
        return state, batch

    # ------------------------------------------------------------- wire ----
    def _group_size(self) -> int:
        return int(self.mesh.shape.get("fsdp", 1)
                   * self.mesh.shape.get("model", 1))

    def _pack_impl(self) -> str:
        return "ref" if self.dcfg.wire_impl == "jnp" else self.dcfg.wire_impl

    def _flatten_rows(self, leaves, dtype):
        """[(R, ...)] -> one (R, D) buffer (zero-size leaves contribute 0
        columns).  R is whatever leading dim the leaves carry — the worker
        count on the stacked wire path, the W * C flattened owner slots on
        the decode path."""
        rows = leaves[0].shape[0] if leaves else self.dcfg.num_workers
        cols = [l.reshape(rows, -1).astype(dtype) for l in leaves]
        if not cols:
            return jnp.zeros((rows, 0), dtype)
        return jnp.concatenate(cols, axis=1)

    def _pad_wire(self, flat):
        """Zero-pad columns so each row splits evenly across the worker's
        (fsdp, model) device group."""
        pad = sh.pad_to_multiple(flat.shape[1], self._group_size())
        if pad != flat.shape[1]:
            flat = jnp.pad(flat, ((0, 0), (0, pad - flat.shape[1])))
        return flat

    def _finish_wire(self, flat):
        """(W, D) codec output -> the exchanged (W, D_pad) buffer.

        Nibble packing happens per device shard INSIDE the exchange's
        shard_map (see _make_exchange), never here: the SPMD partitioner
        miscompiles the strided pack reshape/stack pattern when the wire
        columns are sharded (same XLA:CPU bug family as the RoPE
        split/concat note in dist.sharding), and per-shard packing is what
        a real transport would do anyway."""
        return self._pad_wire(flat)

    def _flatten_wire(self, leaves, dtype):
        """[(W, ...)] -> exchanged (W, D_pad) buffer (flatten + pad)."""
        return self._finish_wire(self._flatten_rows(leaves, dtype))

    def _strip_wire(self, wire, n: int):
        """Received (W, D_pad) uint8 levels -> (W, n) (drop group padding;
        the exchange already unpacked its per-shard nibbles)."""
        return wire[:, :n]

    def _unflatten_wire(self, wire, templates):
        """(R, D_pad) float buffer -> [(R, ...)] leaves with the templates'
        per-row shapes (full-precision GADMM wire; no packing).  R follows
        the buffer, not the templates."""
        out, off = [], 0
        rows = wire.shape[0]
        for t in templates:
            size = int(np.prod(t.shape[1:]))
            out.append(wire[:, off:off + size].reshape((rows,) + t.shape[1:]))
            off += size
        return out

    def _unflatten_cast(self, flat, like_leaves, treedef):
        """(W, D) f32 buffer -> pytree of leaves cast to each leaf's dtype —
        the same final cast quantize_tensor/dequantize_tensor apply, so the
        fused path keeps the sender==receiver bit-sync per leaf."""
        out, off = [], 0
        for t in like_leaves:
            size = int(np.prod(t.shape[1:]))
            out.append(flat[:, off:off + size].reshape(t.shape)
                       .astype(t.dtype))
            off += size
        return jax.tree.unflatten(treedef, out)

    def _port_perms(self) -> list[list[tuple[int, int]]]:
        """One ppermute permutation per edge color — the canonical
        core.topology.edge_schedule (shared with the sim's per-message
        scheduling).  Color class c is a matching, so sending BOTH
        directions of each of its edges is still a valid (partial)
        permutation; workers without a color-c edge receive ppermute's
        zero fill."""
        return edge_schedule(self.topo)

    def _make_exchange(self, sharded: bool):
        """payload pytree of (W, ...) arrays -> tuple over ports.

        result[c][w] = payload[partner of w in edge color c] (zeros where w
        has no color-c edge).  The sharded path sends each device's shard to
        the matching device of the partner worker group with
        jax.lax.ppermute — uint8 payloads stay uint8 on the wire, and with
        pack_wire each device nibble-packs its own shard right before the
        ppermute and unpacks right after (pack4/unpack4 run as purely local
        ops inside the shard_map: halved wire bytes, and no SPMD
        partitioning of the strided pack pattern, which XLA:CPU
        miscompiles).
        """
        w = self.dcfg.num_workers
        topo = self.topo
        ports = topo.num_ports
        if not sharded:
            # Unsharded reference: select by the partner table; packing
            # would be an exact roundtrip (contract-tested in
            # tests/test_kernels.py), so the levels move unpacked.
            partner = topo.port  # (W, C) int, -1 where no edge

            @_layer("exchange")
            def exchange(payload):
                return tuple(
                    jax.tree.map(lambda x: _rows(x, partner[:, c]), payload)
                    for c in range(ports))
            return exchange

        mesh = self.mesh
        perms = self._port_perms()
        pack_impl = self._pack_impl()
        wire_spec = P("worker", ("fsdp", "model"))

        def spec_of(a):
            if a.ndim == 2 and a.shape[1] % self._group_size() == 0:
                return wire_spec
            return P("worker", *(None,) * (a.ndim - 1))

        @_layer("exchange")
        def exchange(payload):
            specs = jax.tree.map(spec_of, payload)
            # which leaves get per-shard nibble packing (bool leaves: a
            # PartitionSpec is a tuple subclass, so specs can't be mapped
            # over as a second operand tree)
            packed_leaves = jax.tree.map(
                lambda x: bool(self.dcfg.pack_wire and x.dtype == jnp.uint8
                               and spec_of(x) == wire_spec), payload)

            def body(p):
                def send(x, do_pack, perm):
                    if do_pack:
                        n_loc = x.size  # local (1, D_pad / group) shard
                        packed = pack_ops.pack4(x.reshape(-1),
                                                impl=pack_impl)
                        recv = jax.lax.ppermute(packed, "worker", perm)
                        return pack_ops.unpack4(
                            recv, n_loc, impl=pack_impl).reshape(x.shape)
                    return jax.lax.ppermute(x, "worker", perm)

                return tuple(
                    jax.tree.map(lambda x, f: send(x, f, perm),
                                 p, packed_leaves)
                    for perm in perms)

            return jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                                 out_specs=(specs,) * ports,
                                 check_vma=False)(payload)

        return exchange

    # ------------------------------------------------------- quantization --
    def _per_leaf_radius(self, leaves, hat_leaves):
        """(W, L) per-leaf inf-norm radii; zero-size leaves get R = 0 (the
        same guard quantizer.global_radius applies)."""
        w = self.dcfg.num_workers
        cols = []
        for x, h in zip(leaves, hat_leaves):
            if int(np.prod(x.shape[1:])) == 0:
                cols.append(jnp.zeros((w,), jnp.float32))
            else:
                cols.append(jax.vmap(lambda a, b: jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))(x, h))
        if not cols:
            return jnp.zeros((w, 0), jnp.float32)
        return jnp.stack(cols, axis=1)

    def _per_leaf_delta2(self, a_leaves, b_leaves):
        """(W, L) per-leaf squared L2 distances — the residual-magnitude
        ranking score of the bit-budget controller and the per-leaf censor
        statistic (zero-size leaves get 0)."""
        w = self.dcfg.num_workers
        cols = []
        for x, h in zip(a_leaves, b_leaves):
            if int(np.prod(x.shape[1:])) == 0:
                cols.append(jnp.zeros((w,), jnp.float32))
            else:
                d = (x.astype(jnp.float32)
                     - h.astype(jnp.float32)).reshape(w, -1)
                cols.append(jnp.sum(d * d, axis=1))
        if not cols:
            return jnp.zeros((w, 0), jnp.float32)
        return jnp.stack(cols, axis=1)

    def _qdq_row(self, theta_row, hat_row, u_row, radius, bits):
        """Quantize one (d,) wire-row slab -> (q, new hat).  radius is a
        scalar (global mode) or a (d,) per-element expansion (per_tensor
        mode); bits is a scalar or a (d,) per-element expansion (layerwise
        per-leaf widths).

        The codec yields q only (the Pallas `quantize` kernel or the jnp
        reference's q); the sender's new hat is `_decode(q)`, the function
        every receiver applies to the same q, so sender==receiver bit-sync
        never rests on Mosaic and XLA rounding the dequantize alike."""
        levels = levels_of(bits)
        radius = jnp.asarray(radius, jnp.float32)
        if self.dcfg.wire_impl == "jnp":
            q, _ = q_ref.quantize_dequantize_ref(
                theta_row, hat_row, u_row, radius, levels)
        else:
            q = q_kernel.quantize(
                theta_row, hat_row, u_row, radius, levels,
                interpret=self.dcfg.wire_impl != "pallas_compiled")
        return q, _decode(q, hat_row, radius, levels)

    def _qdq_sharded(self, theta_f, hat_f, u, radius, bits, seg=None):
        """Codec under shard_map: every device runs the quantize (and the
        shared decode of its hat) on exactly the (1, d_loc) wire slab it owns,
        with its worker's radius/bits riding along the 'worker' axis.

        This keeps the codec internals out of the SPMD partitioner — which
        XLA:CPU miscompiles for the pad/reshape/slice patterns inside the
        kernels (same bug family as the RoPE note in dist.sharding) — and
        is the production TPU layout anyway: local data, local kernel.

        Per-leaf radius/bits (ndim == 2) arrive as the raw (W, L) tables
        plus the static position->leaf map `seg` and expand to per-position
        values INSIDE the body, on each device's own slab.  Expanding
        outside (the old `per_leaf_r[:, seg]` form) hands the partitioner
        a gather whose output is sharded along the gathered dimension,
        which XLA:CPU miscompiles inside the fused step — the sender
        quantized against garbage radii while receivers (whose decode runs
        outside the shard_map, see _decode_slots) used the true ones, so
        every sharded per_tensor/layerwise run silently desynced and the
        consensus residual grew without bound."""
        wspec = P("worker") if self.dcfg.num_workers > 1 else P(None)
        bspec = P(*wspec, ("fsdp", "model"))
        lspec = P(*wspec, None)
        rspec = lspec if radius.ndim == 2 else wspec
        bitspec = lspec if bits.ndim == 2 else wspec
        d_pad = theta_f.shape[1]
        if seg is not None:
            # padding positions -> sentinel leaf L: R = 0 keeps them inert,
            # b = 1 keeps the codec's levels >= 1
            n_leaves = int(radius.shape[1] if radius.ndim == 2
                           else bits.shape[1])
            seg_pad = np.full((d_pad,), n_leaves, np.int32)
            seg_pad[:seg.size] = seg
        msize = self.mesh.shape["model"]

        def body(th, h, uu, rr, bb):
            rr_row, bb_row = rr[0], bb[0]
            if seg is not None:
                d_loc = th.shape[1]
                slab = (jax.lax.axis_index("fsdp") * msize
                        + jax.lax.axis_index("model"))
                seg_loc = jax.lax.dynamic_slice(
                    jnp.asarray(seg_pad), (slab * d_loc,), (d_loc,))
                if rr.ndim == 2:
                    rr_row = jnp.concatenate(
                        [rr_row, jnp.zeros((1,), rr.dtype)])[seg_loc]
                if bb.ndim == 2:
                    bb_row = jnp.concatenate(
                        [bb_row, jnp.ones((1,), bb.dtype)])[seg_loc]
            q, hh = self._qdq_row(th[0], h[0], uu[0], rr_row, bb_row)
            return q[None], hh[None]

        return jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(bspec, bspec, bspec, rspec, bitspec),
            out_specs=(bspec, bspec), check_vma=False)(
                theta_f, hat_f, u, radius, bits)

    def _quantize_all(self, theta, hat, bits_prev, radius_prev, key,
                      sharded: bool, step_idx=None):
        """Quantize every worker row on the flat wire buffer.

        Returns (q_wire (W, D_pad) uint8, hat_new pytree, r_new, b_new,
        leaf_due) with r_new (W,) in global mode / (W, L) per_tensor.  Bit
        adaptation (paper eq. 11) tracks the global radius ratio — or, in
        layerwise mode, each leaf's own ratio, unless the bit-budget
        controller (quantizer.allocate_bits) supersedes it.  leaf_due is
        the (W, L) exchange-period gate in layerwise mode (None otherwise);
        the codec itself always runs on every leaf with the full fresh
        radii, so the shared uniform draw is consumed identically whatever
        the masks — callers zero the PAYLOAD radius of unsent leaves
        instead, which no-ops them on both endpoints.

        Shared uniform-draw convention: ONE jax.random.uniform draw over the
        padded (W, D_pad) buffer, consumed identically by every wire_impl —
        the jnp and Pallas paths are bit-identical.
        """
        qcfg = self.dcfg.gadmm.qcfg
        lw = self.dcfg.layerwise
        w = self.dcfg.num_workers
        leaves = jax.tree.leaves(theta)
        treedef = jax.tree.structure(theta)
        hat_leaves = treedef.flatten_up_to(hat)
        sizes = _leaf_sizes(leaves)
        n_leaves = len(sizes)
        per_leaf_r = self._per_leaf_radius(leaves, hat_leaves)  # (W, L)
        r_global = (jnp.max(per_leaf_r, axis=1) if per_leaf_r.shape[1]
                    else jnp.zeros((w,), jnp.float32))
        leaf_due = None
        if lw is not None:
            base_b, periods, _ = self._lw_tables(tuple(sizes))
            if lw.budget_bits is not None:
                # budget controller: rank leaves by residual magnitude,
                # spend the fixed wire budget best-first
                scores = jnp.sqrt(self._per_leaf_delta2(leaves, hat_leaves))
                b_new = allocate_bits(scores, np.asarray(sizes, np.float32),
                                      lw.budget_bits, lw.min_bits,
                                      lw.max_bits)              # (W, L)
            elif lw.adapt_bits:
                # eq. 11 per leaf: each leaf tracks its own radius ratio
                b_new = _next_bits(self._lw_qcfg, bits_prev, per_leaf_r,
                                   radius_prev, base_bits=base_b[None])
            else:
                b_new = jnp.broadcast_to(base_b[None], (w, n_leaves))
            leaf_due = jnp.broadcast_to((step_idx % periods) == 0,
                                        (w, n_leaves))
            r_new = per_leaf_r
        elif qcfg.adapt_bits:
            r_prev = (radius_prev if radius_prev.ndim == 1
                      else jnp.max(radius_prev, axis=1))
            b_new = _next_bits(qcfg, bits_prev, r_global, r_prev)  # (W,)
        else:
            b_new = jnp.full((w,), qcfg.bits, jnp.int32)
        if lw is None:
            r_new = (r_global if self.dcfg.radius_mode == "global"
                     else per_leaf_r)

        d = sum(sizes)
        if d == 0:
            return (jnp.zeros((w, 0), jnp.uint8),
                    jax.tree.unflatten(treedef, list(hat_leaves)),
                    r_new, b_new, leaf_due)
        theta_f = self._pad_wire(self._flatten_rows(leaves, jnp.float32))
        hat_f = self._pad_wire(self._flatten_rows(hat_leaves, jnp.float32))
        d_pad = theta_f.shape[1]
        u = jax.random.uniform(key, (w, d_pad), jnp.float32)
        per_tensor = self.dcfg.radius_mode == "per_tensor"
        seg = (np.repeat(np.arange(n_leaves), sizes)           # (D,)
               if (per_tensor or lw is not None) else None)
        if sharded:
            # per-leaf (W, L) tables ride into the shard_map untouched and
            # expand to per-position values on each device's local slab —
            # the outside-expansion form below is a gather the SPMD
            # partitioner must shard along the gathered dimension, which
            # XLA:CPU miscompiles (see _qdq_sharded)
            q_wire, hat_new_f = self._qdq_sharded(
                theta_f, hat_f, u,
                per_leaf_r if per_tensor else r_global,
                b_new, seg=seg)
        else:
            if per_tensor:
                # segment-scalar pass: per-leaf scalars -> per-position
                # values; padding positions get R = 0 (codec leaves them
                # untouched)
                r_arg = self._pad_wire(per_leaf_r[:, seg])     # (W, D_pad)
            else:
                r_arg = r_global
            b_arg = b_new
            if lw is not None:
                # per-position bit widths; padding gets b = 1 (levels >= 1
                # — the codec divides by levels; R = 0 keeps them inert)
                b_pos = b_new[:, seg]
                if d_pad > d:
                    b_pos = jnp.pad(b_pos, ((0, 0), (0, d_pad - d)),
                                    constant_values=1)
                b_arg = b_pos                                  # (W, D_pad)
            q_rows, hat_rows = [], []
            for i in range(w):
                q_i, h_i = self._qdq_row(theta_f[i], hat_f[i], u[i],
                                         r_arg[i], b_arg[i])
                q_rows.append(q_i)
                hat_rows.append(h_i)
            q_wire = jnp.stack(q_rows)                 # (W, D_pad) uint8
            hat_new_f = jnp.stack(hat_rows)            # (W, D_pad) f32
        hat_new = self._unflatten_cast(hat_new_f, hat_leaves, treedef)
        return q_wire, hat_new, r_new, b_new, leaf_due

    def _dequantize_all(self, q_wire, hat_copy, radius, bits):
        """Receiver-side reconstruction on the flat wire buffer against the
        stored neighbor hats — the same `_decode` (and per-leaf final cast)
        the sender commits its own hat with, preserving bit-sync."""
        treedef = jax.tree.structure(hat_copy)
        hat_leaves = treedef.flatten_up_to(hat_copy)
        hat_f = self._flatten_rows(hat_leaves, jnp.float32)    # (W, D)
        if hat_f.shape[1] == 0:
            return hat_copy
        sizes = _leaf_sizes(hat_leaves)
        seg = np.repeat(np.arange(len(sizes)), sizes)
        if bits.ndim == 1:
            levels = levels_of(bits)[:, None]
        else:
            # layerwise per-leaf widths -> per-position levels
            levels = levels_of(bits[:, seg])
        r_pos = radius[:, None] if radius.ndim == 1 else radius[:, seg]
        out = _decode(q_wire, hat_f, r_pos, levels)
        return self._unflatten_cast(out, hat_leaves, treedef)

    # ------------------------------------------------------------- step ----
    def _data_loss(self, theta_w, batch_w):
        mb = self.dcfg.microbatches
        if mb <= 1:
            return self.model.loss_fn(theta_w, batch_w, self.cfg)
        split = jax.tree.map(
            lambda a: a.reshape((mb, a.shape[0] // mb) + a.shape[1:]), batch_w)

        def body(acc, b):
            return acc + self.model.loss_fn(theta_w, b, self.cfg), None

        total, _ = jax.lax.scan(body, jnp.zeros(()), split)
        return total / mb

    def _local_loss(self, theta_w, batch_w, lam_nbr, hat_nbr, pmask, sign):
        """Stochastic augmented Lagrangian of eq. 14/16 for one worker.

        lam_nbr / hat_nbr: per-port tuples of this worker's edge duals and
        neighbor-hat reconstructions; pmask[c] = 1.0 iff the worker has a
        color-c edge; sign = +1 for heads, -1 for tails (the edge dual's
        canonical orientation is head -> tail, so the head sees
        <lam, theta - hat_nbr> and the tail <lam, hat_nbr - theta>)."""
        rho = self.dcfg.gadmm.rho
        f = self._data_loss(theta_w, batch_w)
        dual = jnp.zeros(())
        prox = jnp.zeros(())
        for c in range(len(hat_nbr)):
            diff = jax.tree.map(jnp.subtract, theta_w, hat_nbr[c])
            dual = dual + pmask[c] * sign * _tvdot(lam_nbr[c], diff)
            prox = prox + pmask[c] * _tsqnorm(theta_w, hat_nbr[c])
        return f + dual + 0.5 * rho * prox, f

    def _local_opt(self, theta, mu, nu, t, batch_w, lam_nbr, hat_nbr,
                   pmask, sign):
        """local_iters Adam steps on the augmented Lagrangian (one worker)."""
        lr = self.dcfg.local_lr
        grad_fn = jax.value_and_grad(self._local_loss, has_aux=True)

        def body(carry, _):
            th, m, v, tt = carry
            (_, f), g = grad_fn(th, batch_w, lam_nbr, hat_nbr, pmask, sign)
            tt = tt + 1
            tf = tt.astype(jnp.float32)
            m = jax.tree.map(
                lambda mm, gg: _ADAM_B1 * mm + (1 - _ADAM_B1) * gg, m, g)
            v = jax.tree.map(
                lambda vv, gg: _ADAM_B2 * vv + (1 - _ADAM_B2) * gg * gg, v, g)
            th = jax.tree.map(
                lambda t_, mm, vv: (t_ - lr * (mm / (1 - _ADAM_B1 ** tf))
                                    / (jnp.sqrt(vv / (1 - _ADAM_B2 ** tf))
                                       + _ADAM_EPS)).astype(t_.dtype),
                th, m, v)
            return (th, m, v, tt), f

        (theta, mu, nu, t), fs = jax.lax.scan(
            body, (theta, mu, nu, t), None, length=self.dcfg.local_iters)
        return theta, mu, nu, t, fs[0]

    def make_train_step(self):
        """Unsharded (single-process) reference step: identical math to the
        sharded step, neighbor exchange via array shifts instead of ppermute."""
        return self._build_step(sharded=False)

    def jit_train_step(self, state: DistState, batch):
        """Jitted sharded step; state/batch may be arrays or ShapeDtypeStructs
        (AOT lowering for dry runs)."""
        ss = self._shardings(self.state_specs(state))
        bs = self._shardings(self.batch_specs(batch))
        return jax.jit(self._build_step(sharded=True),
                       in_shardings=(ss, bs), out_shardings=(ss, None))

    def phase_compute(self, st, batch, active, key, step_idx,
                      sharded: bool = False, port_weights=None, rows=None):
        """Local Adam + quantize (+ censor) for the active workers;
        returns the updated state and the wire payload (exchange NOT yet
        applied).  payload['sent'] is the per-worker transmit flag — the
        1-bit censor sideband that rides every link.  In layerwise mode
        payload['leaf_sent'] is the effective (W, L) per-leaf transmit
        mask (accounting only — receivers need nothing beyond the
        leaf-masked radius sideband; _build_step pops it before the
        exchange).

        `port_weights` (W, C) overrides the 0/1 port mask weighting the
        neighbor dual/prox terms of the local loss — partial
        participation passes degree-renormalized weights that drop
        absent neighbors' frozen hats (None = self.pmask, the full
        topology).

        Worker row w of every output depends only on row w of the inputs
        (plus the shared uniform-draw key), so a single worker can replay
        its own row from a local view whose other rows are garbage — the
        contract repro.sim.worker.TrainerActor builds on.

        `rows` (static indices, ascending) runs the local solve on those
        workers alone and commits only their rows: `active` is cut to
        them, every other row of the state is returned as it came in and
        sends nothing, and f0 is 0 there.  None solves every row (the
        simulator's dynamic masks need that).  The codec still codes all
        W rows: its uniform draw spans (W, D)."""
        g = self.dcfg.gadmm
        cc = self.dcfg.censor
        w = self.dcfg.num_workers
        pw = self.pmask if port_weights is None else port_weights
        (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t) = st
        if rows is None:
            sub = lambda tree: tree
            put = lambda full, part: part
        else:
            rows = np.asarray(rows)
            active = active & jnp.asarray(np.isin(np.arange(w), rows))
            sub = lambda tree: jax.tree.map(lambda a: _rows(a, rows), tree)
            put = lambda full, part: jax.tree.map(
                lambda a, b: _put_rows(a, rows, b), full, part)
        with _layer("local_solve"):
            act = sub(active)
            old = sub((theta, mu, nu, t))
            new_theta, new_mu, new_nu, new_t, f0 = jax.vmap(self._local_opt)(
                *old, sub(batch), self._port_view(lam_nbr, rows),
                self._port_view(hat_nbr, rows), sub(pw), sub(self.sign))
            theta = put(theta, _twhere(act, new_theta, old[0]))
            mu = put(mu, _twhere(act, new_mu, old[1]))
            nu = put(nu, _twhere(act, new_nu, old[2]))
            t = put(t, jnp.where(act, new_t, old[3]))
            f0 = put(jnp.zeros((w,), f0.dtype), f0)

        with _layer("codec"):
            if g.quantize:
                q_wire, hat_new, r_new, b_new, leaf_due = self._quantize_all(
                    theta, hat, bits, radius, key, sharded, step_idx)
                lw = self.dcfg.layerwise
                if lw is not None:
                    # L-FGADMM leaf gating: a leaf is transmitted only on its
                    # period rounds, and (with per-leaf taus) only when its
                    # committed quantized delta moved past the decaying
                    # threshold.  The candidate hat is the per-leaf mix of
                    # new/old — what would actually be committed — so the
                    # worker-level censor below sees the true delta and both
                    # endpoints stay bit-synced (unsent leaves ride the payload
                    # with radius 0, a codec no-op for every receiver).
                    treedef = jax.tree.structure(hat)
                    hn = treedef.flatten_up_to(hat_new)
                    ho = treedef.flatten_up_to(hat)
                    leaf_sent = leaf_due
                    _, _, taus = self._lw_tables(
                        tuple(_leaf_sizes(jax.tree.leaves(theta))))
                    if taus is not None:
                        thr = taus * jnp.power(
                            jnp.float32(lw.tau_xi),
                            jnp.asarray(step_idx, jnp.float32))    # (L,)
                        delta = jnp.sqrt(self._per_leaf_delta2(hn, ho))
                        leaf_sent = leaf_sent & (delta > thr)
                    hat_cand = jax.tree.unflatten(treedef, [
                        jnp.where(_bmask(leaf_sent[:, i], a), a, b)
                        for i, (a, b) in enumerate(zip(hn, ho))])
                    if cc is not None:
                        sent = active & censor_mod.transmit_mask(
                            hat_cand, hat, cc, step_idx)
                    else:
                        sent = active
                    eff_leaf = leaf_sent & sent[:, None]           # (W, L)
                    hat = _twhere(sent, hat_cand, hat)
                    radius = jnp.where(eff_leaf, r_new, radius)
                    bits = jnp.where(eff_leaf, b_new, bits)
                    payload = {"wire": self._finish_wire(q_wire),
                               "radius": jnp.where(eff_leaf, r_new, 0.0),
                               "bits": b_new, "sent": sent,
                               "leaf_sent": eff_leaf}
                else:
                    if cc is not None:
                        # CQ-GGADMM censoring: commit + transmit only when
                        # the quantized model moved past the decaying
                        # threshold.  hat_new is the committed (per-leaf-cast)
                        # value, so the mask is identical for every wire_impl
                        # and on both the unsharded and sharded paths.
                        sent = active & censor_mod.transmit_mask(
                            hat_new, hat, cc, step_idx)
                    else:
                        sent = active
                    hat = _twhere(sent, hat_new, hat)
                    radius = jnp.where(_bmask(sent, r_new), r_new, radius)
                    bits = jnp.where(sent, b_new, bits)
                    payload = {"wire": self._finish_wire(q_wire),
                               "radius": r_new, "bits": b_new, "sent": sent}
            else:
                # full-precision GADMM: track the would-be radius for metrics,
                # then "transmit" theta itself (hat == theta).  Censoring
                # applies identically (this is C-GGADMM).
                per_leaf_r = self._per_leaf_radius(
                    jax.tree.leaves(theta), jax.tree.leaves(hat))  # (W, L)
                if cc is not None:
                    sent = active & censor_mod.transmit_mask(
                        theta, hat, cc, step_idx)
                else:
                    sent = active
                hat = _twhere(sent, theta, hat)
                r_new = (jnp.max(per_leaf_r, axis=1)
                         if radius.ndim == 1 and per_leaf_r.shape[1]
                         else (per_leaf_r if radius.ndim > 1
                               else jnp.zeros((w,), jnp.float32)))
                radius = jnp.where(_bmask(sent, r_new), r_new, radius)
                payload = {"wire": self._flatten_wire(
                    jax.tree.leaves(hat), jnp.float32), "sent": sent}

        return (theta, hat, hat_nbr, lam_nbr, radius, bits,
                mu, nu, t), payload, f0

    def _decode_slots(self, recv, slots):
        """Decode the per-color exchanged payloads into the owner slots:
        slot (w, c) takes recv[c][w], the payload w received from its
        color-c partner.  The C colors stack along the slot dim, so the
        decode is elementwise over worker-sharded operands: each chip
        decodes its own workers' C slots, with no gather.  A partner that
        stayed silent (censored, inactive, or no color-c edge: the
        exchange's zero fill) leaves the slot as it was."""
        n = self.dcfg.num_workers * self.topo.num_ports
        col = lambda k: jnp.stack([r[k] for r in recv], axis=1).reshape(
            (n,) + recv[0][k].shape[1:])
        d = sum(int(np.prod(l.shape[1:])) for l in jax.tree.leaves(slots))
        side = ((col("radius"), col("bits")) if self.dcfg.gadmm.quantize
                else (None, None))
        return self._decode_rows(self._strip_wire(col("wire"), d), slots,
                                 col("sent"), *side)

    @_layer("decode")
    def phase_apply(self, st, recv):
        """Fold the exchanged payloads into the owner-indexed neighbor hats
        (`_decode_slots`).  Censored (or phase-inactive) partners leave
        the stored hat untouched — exactly what their own rolled-back
        state holds, preserving bit-sync."""
        (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t) = st
        if self.eidx.num_directed == 0:
            return st
        hat_nbr = self._decode_slots(recv, hat_nbr)
        return (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t)

    def _dual_step(self, lam_nbr, own, hat_nbr, coef):
        """lam[w, c] += alpha * rho * coef[w, c] * (own[w] - hat[w, c]):
        elementwise over the owner slots, own repeated over each worker's
        slots."""
        scale = self.dcfg.gadmm.alpha * self.dcfg.gadmm.rho
        return jax.tree.map(
            lambda l, a, b: l + scale * _bmask(coef, l).astype(l.dtype)
            * (a.astype(l.dtype) - b.astype(l.dtype)),
            lam_nbr, self._per_slot(own), hat_nbr)

    @_layer("dual")
    def dual_update(self, st, edge_mask=None):
        """Damped dual update (eq. 18) from reconstructed hats; both ends
        of each edge apply the same increment, keeping duals in sync:
        lam_e += a*rho*(hat_head - hat_tail), which the head computes
        as +(own - nbr) and the tail as -(own - nbr) — per owner slot
        (w, c) that is sign[w] * (hat[w] - hat_nbr[w, c]), each chip on
        its own workers' slots.

        `edge_mask` (2E,), in edge_index order, zeroes selected directed
        edges — the simulator masks edges whose far endpoint dropped
        (freezing those duals instead of integrating a stale residual
        forever), partial participation edges with an absent endpoint."""
        (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t) = st
        if self.eidx.num_directed == 0:
            return st
        coef = (self._slot_sign if edge_mask is None
                else self._slot_sign * self._edge_to_slots(edge_mask))
        lam_nbr = self._dual_step(lam_nbr, hat, hat_nbr, coef)
        return (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t)

    def _groups(self, sharded: bool):
        """Static (heads, tails) rows each Gauss-Seidel phase solves, or
        (None, None) to solve every row in both.  Where all workers share
        one device a phase solves its own group alone, so each worker
        runs one local solve a round.  On a worker-sharded mesh every
        chip solves its row in both phases: the idle phase costs the same
        wall time, and a row subset would move rows between chips."""
        heads = np.flatnonzero(self.topo.head_mask)
        tails = np.flatnonzero(~self.topo.head_mask)
        if sharded or not (heads.size and tails.size):
            return None, None
        return heads, tails

    def local_solves(self, sharded: bool) -> int:
        """Worker local solves one round runs: W where each worker is
        solved once (Jacobi; Gauss-Seidel with the workers on one
        device), 2W where every row is solved in both phases."""
        w = self.dcfg.num_workers
        stale = self.dcfg.staleness > 0 and w > 1 and self.topo.num_edges > 0
        two_phase = stale or (self.dcfg.mode == "gauss-seidel" and w > 1)
        return 2 * w if two_phase and self._groups(sharded)[0] is None else w

    def _merge_f0(self, f0_h, f0_t, rows_h):
        """The round's start-of-round data losses: all of phase 1's when it
        solved every row, else each group's from its own phase (a tail's
        theta and batch are the same at the start of both phases)."""
        return f0_h if rows_h is None else jnp.where(self.is_head, f0_h, f0_t)

    def _build_step(self, sharded: bool):
        dcfg = self.dcfg
        g = dcfg.gadmm
        cc = dcfg.censor
        w = dcfg.num_workers
        topo = self.topo
        ports = topo.num_ports
        if sharded and "worker" in self.mesh.shape:
            assert self.mesh.shape["worker"] == w, (
                f"mesh worker axis {self.mesh.shape['worker']} != "
                f"num_workers {w}")
        is_head = self.is_head
        all_on = jnp.ones((w,), bool)
        exchange = (self._make_exchange(sharded) if topo.num_edges else None)
        phase_compute = functools.partial(self.phase_compute, sharded=sharded)
        rows_h, rows_t = self._groups(sharded)

        port_idx = jnp.asarray(topo.port, jnp.int32) if ports else None

        @_layer("metrics")
        def participation_masks(round_key):
            """Per-round shared-knowledge participation draw: (W,) bool
            mask, degree-renormalized (W, C) port weights, and the (2E,)
            both-endpoints edge gate.  Derived by fold_in from the round
            key (NOT by splitting it) so the participation=1.0 key
            stream — and every committed golden — is untouched."""
            part = jax.random.bernoulli(
                jax.random.fold_in(round_key, 0x9A77), dcfg.participation,
                (w,))
            if port_idx is None:
                return part, self.pmask, None
            nbr_part = (part[jnp.maximum(port_idx, 0)].astype(jnp.float32)
                        * self.pmask)                          # (W, C)
            deg = jnp.sum(self.pmask, axis=1)
            present = jnp.sum(nbr_part, axis=1)
            pw = nbr_part * (deg / jnp.maximum(present, 1.0))[:, None]
            edge_part = None
            if self.eidx.num_directed:
                edge_part = (part[self._d_src]
                             & part[self._d_dst]).astype(jnp.float32)
            return part, pw, edge_part

        def step(state: DistState, batch):
            key, k1, k2 = jax.random.split(state.key, 3)
            st = (state.theta, state.theta_hat, state.hat_nbr,
                  state.lam_nbr, state.radius, state.bits, state.opt_mu,
                  state.opt_nu, state.opt_t)
            sent_phases = []
            leaf_phases = []   # layerwise: (eff_leaf, bits) per phase
            inbox, hat_lag = state.inbox, state.hat_lag
            part = pw = edge_part = None
            if dcfg.participation < 1.0:
                part, pw, edge_part = participation_masks(state.key)
            mask = (lambda a: a) if part is None else (lambda a: a & part)

            def phase(st, active, k, rows=None):
                st, payload, f0 = phase_compute(st, batch, mask(active), k,
                                                state.step, port_weights=pw,
                                                rows=rows)
                sent_phases.append(payload["sent"])
                lf = payload.pop("leaf_sent", None)
                if lf is not None:
                    leaf_phases.append((lf, payload["bits"]))
                if exchange is not None:
                    st = self.phase_apply(st, exchange(payload))
                return st, f0

            stale = (dcfg.staleness > 0 and w > 1 and topo.num_edges > 0)
            if stale:
                # pipelined exchange: decode the round-(k-S) inbox entry
                # (recv-done), run BOTH phases against those S-stale hats,
                # dual-update on matching S-stale snapshots, then push this
                # round's merged payload into the in-flight ring (send /
                # recv-start).  Wire bits are billed below on THIS round —
                # the round the payload is sent — never on the round it is
                # eventually consumed.
                (st, hat_lag, f0, sent_phases, leaf_phases,
                 inbox) = self._stale_round(
                    st, batch, state, hat_lag, k1, k2, sharded, exchange,
                    part=part, port_weights=pw, edge_part=edge_part)
            elif dcfg.mode == "gauss-seidel" and w > 1 and dcfg.overlap:
                # double-buffered exchange: put the heads' payload on the
                # wire, run the tails' local iterations against the PREVIOUS
                # neighbor hats while it is in flight, then fold both
                # exchanges in.  XLA sees no data dependence between the
                # heads' ppermute and the tails' compute, so the graph
                # latency hides behind the Adam iterations.
                st, pl_h, f0_h = phase_compute(st, batch, mask(is_head), k1,
                                               state.step, port_weights=pw,
                                               rows=rows_h)
                sent_phases.append(pl_h["sent"])
                lf = pl_h.pop("leaf_sent", None)
                if lf is not None:
                    leaf_phases.append((lf, pl_h["bits"]))
                recv_h = exchange(pl_h)
                st, pl_t, f0_t = phase_compute(st, batch, mask(~is_head), k2,
                                               state.step, port_weights=pw,
                                               rows=rows_t)
                f0 = self._merge_f0(f0_h, f0_t, rows_h)
                sent_phases.append(pl_t["sent"])
                lf = pl_t.pop("leaf_sent", None)
                if lf is not None:
                    leaf_phases.append((lf, pl_t["bits"]))
                st = self.phase_apply(st, recv_h)
                st = self.phase_apply(st, exchange(pl_t))
                st = self.dual_update(st, edge_mask=edge_part)
            elif dcfg.mode == "gauss-seidel" and w > 1:
                st, f0_h = phase(st, is_head, k1, rows_h)
                st, f0_t = phase(st, ~is_head, k2, rows_t)
                f0 = self._merge_f0(f0_h, f0_t, rows_h)
                st = self.dual_update(st, edge_mask=edge_part)
            else:
                st, f0 = phase(st, all_on, k1)
                st = self.dual_update(st, edge_mask=edge_part)
            (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t) = st

            with _layer("metrics"):
                # consensus violation, each edge counted once (from its
                # head's slot): each chip sums its own workers' slots, only
                # the scalar is reduced across chips
                resid_sq = jnp.zeros(())
                head_slot = self._slot_sign > 0                  # (W * C,)
                if self.eidx.num_directed:
                    resid_sq = resid_sq + sum(jax.tree.leaves(jax.tree.map(
                        lambda a, b: jnp.sum(_bmask(head_slot, b)
                                             * (a.astype(jnp.float32)
                                                - b.astype(jnp.float32)) ** 2),
                        self._per_slot(hat), hat_nbr)))
                sent_total = sum(jnp.sum(s.astype(jnp.float32))
                                 for s in sent_phases)
                metrics = {
                    "loss": jnp.mean(f0),
                    "consensus_resid": jnp.sqrt(resid_sq),
                    "radius_mean": jnp.mean(radius),
                    "bits_mean": jnp.mean(bits.astype(jnp.float32)),
                    # every worker is transmit-eligible exactly once per round
                    "skip_rate": 1.0 - sent_total / w,
                    "wire_bits_per_round": jnp.asarray(
                        self.wire_bits_per_round(
                            theta,
                            sent_phases
                            if (cc is not None or dcfg.participation < 1.0)
                            else None,
                            leaf_phases if dcfg.layerwise is not None
                            else None),
                        jnp.float32),
                }
                if dcfg.telemetry:
                    sp = (sent_phases
                          if (cc is not None or dcfg.participation < 1.0)
                          else None)
                    lp = leaf_phases if dcfg.layerwise is not None else None
                    pay, hdr, flg = self.wire_bits_components(theta, sp, lp)
                    deg = jnp.asarray(topo.degree, jnp.float32)
                    sent_any = (sum(s.astype(jnp.float32)
                                    for s in sent_phases)
                                if sent_phases
                                else jnp.zeros((w,), jnp.float32))
                    dual_sq = jnp.zeros(())
                    if self.eidx.num_directed:
                        dual_sq = dual_sq + sum(jax.tree.leaves(jax.tree.map(
                            lambda a, b: jnp.sum(
                                _bmask(head_slot, a)
                                * (a.astype(jnp.float32)
                                   - b.astype(jnp.float32)) ** 2),
                            lam_nbr, state.lam_nbr)))
                    metrics.update({
                        "wire_bits_payload": jnp.asarray(pay, jnp.float32),
                        "wire_bits_header": jnp.asarray(hdr, jnp.float32),
                        "wire_bits_flags": jnp.asarray(flg, jnp.float32),
                        # directed links that carried payload / stayed silent
                        "tx_links": jnp.asarray(
                            sum(jnp.sum(s.astype(jnp.float32) * deg)
                                for s in sent_phases), jnp.float32),
                        "skip_links": jnp.sum((1.0 - sent_any) * deg),
                        # (W,) per-worker transmit mask: per-edge censor skip
                        # counts expand host-side via the static edge index
                        "worker_sent": sent_any,
                        "dual_resid": jnp.sqrt(dual_sq),
                        "participants": (jnp.sum(part.astype(jnp.float32))
                                         if part is not None
                                         else jnp.asarray(float(w),
                                                          jnp.float32)),
                    })
                    if dcfg.layerwise is not None:
                        # (L,) mean allocated bits per leaf across workers
                        metrics["leaf_bits"] = jnp.mean(
                            bits.astype(jnp.float32), axis=0)
            new_state = DistState(
                theta=theta, theta_hat=hat, hat_nbr=hat_nbr,
                lam_nbr=lam_nbr, radius=radius, bits=bits,
                opt_mu=mu, opt_nu=nu, opt_t=t, key=key, step=state.step + 1,
                edge_slot=state.edge_slot, inbox=inbox, hat_lag=hat_lag)
            return new_state, metrics

        return step

    # ------------------------------------------------- staleness pipeline --
    def _decode_rows(self, wire, prev, sent, radius=None, bits=None):
        """Decode stripped wire rows against stored prev rows; rows whose
        sender stayed silent (sent False) keep prev.  The shared decode of
        the neighbor slots (`_decode_slots`, barriered or at the staleness
        pipeline's recv-done) and of the own-hat lag, so a pipeline replays
        the exact bytes the S=0 exchange would.

        On the quantized wire a silent row decodes with R = 0, the codec's
        no-op, which returns prev bitwise: no select over the whole state,
        which XLA:TPU compiles very slowly."""
        if self.dcfg.gadmm.quantize:
            return self._dequantize_all(
                wire, prev, jnp.where(_bmask(sent, radius), radius, 0.0),
                bits)
        treedef = jax.tree.structure(prev)
        leaves = treedef.flatten_up_to(prev)
        ls = self._unflatten_wire(wire, leaves)
        return _twhere(sent, jax.tree.unflatten(
            treedef, [l.astype(r.dtype) for l, r in zip(ls, leaves)]), prev)

    def _stale_round(self, st, batch, state: DistState, hat_lag, k1, k2,
                     sharded: bool, exchange, part=None, port_weights=None,
                     edge_part=None):
        """One staleness-S round: recv-done on the oldest inbox entry (the
        per-color exchange moves it to the partners, who decode it into
        their slots), both compute phases against the S-stale hats,
        fresh-edge-gated dual update on matching S-stale snapshots, send
        into the ring.  With
        partial participation the round's shared mask gates the compute
        phases (`part`), reweights the neighbor sums (`port_weights`) and
        joins the fresh-edge gate on the dual (`edge_part`) — absent
        workers push a sent=False entry into the ring, so their slot is
        silent when it reaches recv-done S rounds later."""
        dcfg = self.dcfg
        s_depth = dcfg.staleness
        phase_compute = functools.partial(self.phase_compute, sharded=sharded,
                                          port_weights=port_weights)

        # ---- recv-done: decode the round-(k-S) entry -----------------
        entry = jax.tree.map(lambda a: a[0], state.inbox)
        recv = exchange(entry)
        with _layer("decode"):
            (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t) = st
            hat_nbr = self._decode_slots(recv, hat_nbr)
            # own-hat snapshot, decoded from the SAME payload stream the
            # neighbors decode — hat_lag[w] stays bitwise-equal to every
            # partner's slot holding w, so dual mirrors cannot drift
            hat_lag = self._decode_rows(entry["wire"], hat_lag, entry["sent"],
                                        entry["radius"], entry["bits"])
        st = (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t)

        # ---- compute: both phases against the S-stale hats -----------
        act_h = self.is_head if part is None else self.is_head & part
        act_t = ~self.is_head if part is None else ~self.is_head & part
        rows_h, rows_t = self._groups(sharded)
        st, pl_h, f0_h = phase_compute(st, batch, act_h, k1, state.step,
                                       rows=rows_h)
        st, pl_t, f0_t = phase_compute(st, batch, act_t, k2, state.step,
                                       rows=rows_t)
        f0 = self._merge_f0(f0_h, f0_t, rows_h)
        sent_phases = [pl_h["sent"], pl_t["sent"]]
        leaf_phases = []
        for pl in (pl_h, pl_t):
            lf = pl.pop("leaf_sent", None)
            if lf is not None:
                leaf_phases.append((lf, pl["bits"]))

        # ---- dual: S-stale own hat vs S-stale neighbor hat, gated off
        # during the S pipeline-fill rounds (both sides are still the
        # zero init then, so the gate is belt-and-braces explicitness —
        # the sim's fresh-edge rule promoted to the trainer)
        (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t) = st
        with _layer("dual"):
            fresh = (state.step >= s_depth).astype(jnp.float32)
            coef = self._slot_sign * fresh
            if edge_part is not None:
                coef = coef * self._edge_to_slots(edge_part)
            lam_nbr = self._dual_step(lam_nbr, hat_lag, hat_nbr, coef)
        st = (theta, hat, hat_nbr, lam_nbr, radius, bits, mu, nu, t)

        # ---- send / recv-start: merge the two phases' payloads (phases
        # partition the workers, so row w comes from exactly one) and
        # push into the ring; the oldest entry just consumed falls out
        with _layer("exchange"):
            d = sum(_leaf_sizes(jax.tree.leaves(theta)))
            mix = lambda a, b: jnp.where(_bmask(self.is_head, a), a, b)
            w_arr = state.inbox["radius"]
            merged = {
                "wire": mix(self._strip_wire(pl_h["wire"], d),
                            self._strip_wire(pl_t["wire"], d)),
                "radius": (mix(pl_h["radius"], pl_t["radius"])
                           if "radius" in pl_h else jnp.zeros_like(w_arr[0])),
                "bits": (mix(pl_h["bits"], pl_t["bits"]) if "bits" in pl_h
                         else jnp.zeros_like(state.inbox["bits"][0])),
                "sent": pl_h["sent"] | pl_t["sent"],
            }
            inbox = jax.tree.map(
                lambda buf, new: jnp.concatenate([buf[1:], new[None]], axis=0),
                state.inbox, merged)
        return st, hat_lag, f0, sent_phases, leaf_phases, inbox

    # ------------------------------------------------------- accounting ----
    def wire_row_bytes(self, d: int) -> int:
        """Actual bytes of one worker's exchanged wire-buffer row for d
        parameters — exactly what the ppermute moves: the row is zero-padded
        to the device-group multiple, and with pack_wire each of the group's
        devices nibble-packs its own D_pad/G shard (packed_len per shard, so
        the pack4 256-level granularity is paid per device)."""
        g = self._group_size()
        d_pad = sh.pad_to_multiple(d, g)
        if self.dcfg.gadmm.quantize:
            if self.dcfg.pack_wire:
                return g * packed_len(d_pad // g)
            return d_pad
        return 4 * d_pad

    def wire_bits_per_round(self, theta, sent_phases=None, leaf_phases=None):
        """Graph traffic per train step, matching the bytes on the wire.

        Without censoring (sent_phases=None) this bills what the ppermute
        exchanges actually move — a static int: per phase (2 in
        gauss-seidel, 1 in jacobi; overlap still performs both phases'
        exchanges) and per direction, each of the topology's E edges carries
        one wire-buffer row (wire_row_bytes: packing + group padding
        included) plus the quantizer sideband (quantizer.header_bits: R one
        f32 in global mode, one per tensor in per_tensor mode, plus the b
        i32).  For the chain E = W-1, the original accounting.  tests
        cross-check this against the constructed payload buffers and
        core.comm_model.

        With censoring, `sent_phases` is the list of per-phase (W,) transmit
        masks and the result is a traced scalar modelling the censored
        protocol: every directed edge always carries the 1-bit censor flag
        (censor.FLAG_BITS), and a direction's payload moves only when its
        source worker transmitted — a worker that is phase-inactive or
        censored is silent.  Directed payloads with source w per phase =
        deg(w) when sent[w], so the payload term is per_link *
        sum_w sent[w]*deg[w].

        In layerwise mode, `leaf_phases` is the list of per-phase
        (eff_leaf (W, L) bool, bits (W, L) i32) pairs and the billing is
        per transmitted leaf on the kernels/pack MIXED wire format
        (pack_mixed framing, the accounting twin of mixed_packed_len):
        every leaf slot carries a 1-bit flag on every directed edge, and a
        transmitted leaf costs 8 * bytes_l + header_bits() where bytes_l is
        packed_len(d_l) at <= 4 bits (nibble-packed segment) and d_l above
        (byte-wide), each sent leaf carrying its own (R f32, b i32) header.
        Group padding is not billed — the mixed format frames exact leaf
        sizes."""
        w = self.dcfg.num_workers
        n_edges = self.topo.num_edges
        if n_edges == 0:
            return 0
        leaves = jax.tree.leaves(theta)
        if leaf_phases is not None:
            sizes = _leaf_sizes(leaves)
            n_leaves = len(sizes)
            bytes_pk = jnp.asarray([packed_len(int(n)) for n in sizes],
                                   jnp.float32)
            bytes_raw = jnp.asarray(sizes, jnp.float32)
            deg = jnp.asarray(self.topo.degree, jnp.float32)
            total = jnp.zeros(())
            for eff, b in leaf_phases:
                bytes_l = jnp.where(b <= 4, bytes_pk, bytes_raw)  # (W, L)
                link = jnp.sum(eff.astype(jnp.float32)
                               * (8.0 * bytes_l + header_bits()), axis=1)
                total = (total
                         + 2 * n_edges * n_leaves * censor_mod.FLAG_BITS
                         + jnp.sum(deg * link))
            return total
        d = sum(_leaf_sizes(leaves))
        row_bits = 8 * self.wire_row_bytes(d)
        if self.dcfg.gadmm.quantize:
            n_r = (len(leaves) if self.dcfg.radius_mode == "per_tensor"
                   else 1)
            sideband = header_bits(num_radii=n_r)
        else:
            sideband = 0
        per_link = row_bits + sideband
        if sent_phases is None:
            n_phases = 2 if self.dcfg.mode == "gauss-seidel" else 1
            return n_phases * 2 * n_edges * per_link
        deg = jnp.asarray(self.topo.degree, jnp.float32)
        total = jnp.zeros(())
        for sent in sent_phases:
            total = (total + 2 * n_edges * censor_mod.FLAG_BITS
                     + per_link * jnp.sum(sent.astype(jnp.float32) * deg))
        return total

    def wire_bits_components(self, theta, sent_phases=None,
                             leaf_phases=None):
        """``wire_bits_per_round`` split into its (payload, header, flags)
        terms — the repro.obs telemetry/invariant decomposition.  Mirrors
        the three billing branches above argument-for-argument;
        payload + header + flags reassembles the total (bit-exactly on
        the static branch, up to float summation order on the traced
        censored/layerwise branches — obs.checks compares under a 1e-6
        relative tolerance).  Kept separate from ``wire_bits_per_round``
        so the committed exact-accounting expectations never change."""
        n_edges = self.topo.num_edges
        zero = jnp.zeros(())
        if n_edges == 0:
            return zero, zero, zero
        leaves = jax.tree.leaves(theta)
        if leaf_phases is not None:
            sizes = _leaf_sizes(leaves)
            n_leaves = len(sizes)
            bytes_pk = jnp.asarray([packed_len(int(n)) for n in sizes],
                                   jnp.float32)
            bytes_raw = jnp.asarray(sizes, jnp.float32)
            deg = jnp.asarray(self.topo.degree, jnp.float32)
            pay, hdr, flg = zero, zero, 0.0
            for eff, b in leaf_phases:
                bytes_l = jnp.where(b <= 4, bytes_pk, bytes_raw)  # (W, L)
                e = eff.astype(jnp.float32)
                pay = pay + jnp.sum(deg * jnp.sum(e * 8.0 * bytes_l,
                                                  axis=1))
                hdr = hdr + jnp.sum(deg * jnp.sum(e, axis=1)
                                    * header_bits())
                flg += 2 * n_edges * n_leaves * censor_mod.FLAG_BITS
            return pay, hdr, jnp.asarray(float(flg))
        d = sum(_leaf_sizes(leaves))
        row_bits = 8 * self.wire_row_bytes(d)
        if self.dcfg.gadmm.quantize:
            n_r = (len(leaves) if self.dcfg.radius_mode == "per_tensor"
                   else 1)
            sideband = header_bits(num_radii=n_r)
        else:
            sideband = 0
        if sent_phases is None:
            n_phases = 2 if self.dcfg.mode == "gauss-seidel" else 1
            links = n_phases * 2 * n_edges
            return (jnp.asarray(float(row_bits * links)),
                    jnp.asarray(float(sideband * links)), zero)
        deg = jnp.asarray(self.topo.degree, jnp.float32)
        links = sum(jnp.sum(s.astype(jnp.float32) * deg)
                    for s in sent_phases)
        flg = len(sent_phases) * 2 * n_edges * censor_mod.FLAG_BITS
        return row_bits * links, sideband * links, jnp.asarray(float(flg))
