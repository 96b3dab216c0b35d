"""Layerwise (L-FGADMM) per-leaf wire contracts of the distributed trainer.

Per-leaf bit widths / exchange periods / censor thresholds and the adaptive
bit-budget controller: uniform-defaults equivalence, jnp vs pallas bitwise
parity composed with censoring / staleness / participation, period masking
semantics (receiver holds the last hat), budget conservation, eq. 11 per-leaf
adaptation, and the layerwise wire accounting against its closed form.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core.censor import FLAG_BITS, CensorConfig
from repro.core.gadmm import GADMMConfig
from repro.core.quantizer import (LayerwiseConfig, QuantizerConfig,
                                  allocate_bits, header_bits)
from repro.dist.qgadmm import DistConfig, QGADMMTrainer, init_state
from repro.kernels.pack.ref import packed_len

# MixedModel leaf order (jax.tree.leaves of the params dict, sorted keys):
# bias (3,), empty (0,), wa (24,), wb (12,) -> per-leaf tuples index this.
LEAF_SIZES = (3, 0, 24, 12)


class MixedModel:
    """Mixed-precision pytree: f32 + bf16 leaves plus a zero-size leaf."""

    @staticmethod
    def init(key, cfg):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "wa": jax.random.normal(k1, (6, 4), jnp.float32),
            "wb": (0.1 * jax.random.normal(k2, (4, 3))).astype(jnp.bfloat16),
            "bias": jax.random.normal(k3, (3,), jnp.float32),
            "empty": jnp.zeros((0,), jnp.float32),
        }

    @staticmethod
    def loss_fn(params, batch, cfg):
        h = batch["x"] @ params["wa"]
        h = h @ params["wb"].astype(jnp.float32) + params["bias"]
        return jnp.mean((h.sum(-1) - batch["y"]) ** 2)


def _setup(w=4, **dcfg_kw):
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("worker", "fsdp", "model"))
    kw = dict(num_workers=w,
              gadmm=GADMMConfig(rho=0.5, quantize=True,
                                qcfg=QuantizerConfig(bits=4), alpha=0.01),
              local_iters=2, local_lr=1e-2)
    kw.update(dcfg_kw)
    dcfg = DistConfig(**kw)
    tr = QGADMMTrainer(MixedModel, None, dcfg, mesh)
    state = init_state(lambda k: MixedModel.init(k, None),
                       jax.random.PRNGKey(0), dcfg)
    batch = {"x": jax.random.normal(jax.random.PRNGKey(1), (w, 8, 6)),
             "y": jax.random.normal(jax.random.PRNGKey(2), (w, 8))}
    return tr, state, batch


def _run(tr, state, batch, steps=3):
    step = jax.jit(tr.make_train_step())
    metrics = None
    for _ in range(steps):
        state, metrics = step(state, batch)
    return state, metrics


def _assert_states_equal(st_a, st_b, fields=None):
    for field in fields or st_a._fields:
        la = jax.tree.leaves(getattr(st_a, field))
        lb = jax.tree.leaves(getattr(st_b, field))
        for a, b in zip(la, lb):
            np.testing.assert_array_equal(
                np.asarray(a).view(np.uint8) if a.dtype == jnp.bfloat16
                else np.asarray(a),
                np.asarray(b).view(np.uint8) if b.dtype == jnp.bfloat16
                else np.asarray(b),
                err_msg=f"state field {field} diverged")


def test_layerwise_defaults_equal_uniform():
    """LayerwiseConfig() (all periods 1, bits from QuantizerConfig, no
    thresholds) reproduces the uniform per_tensor trajectory bitwise — the
    per-leaf codec path is the same arithmetic when every leaf looks alike."""
    tr_u, st_u, batch = _setup(radius_mode="per_tensor")
    tr_l, st_l, _ = _setup(layerwise=LayerwiseConfig())
    st_u, m_u = _run(tr_u, st_u, batch)
    st_l, m_l = _run(tr_l, st_l, batch)
    # bits differ in shape ((W,) vs (W, L)) by design; everything else and
    # the model trajectory must match bitwise
    _assert_states_equal(st_u, st_l, fields=("theta", "theta_hat",
                                             "hat_nbr", "lam_nbr",
                                             "radius"))
    np.testing.assert_array_equal(np.asarray(m_u["loss"]),
                                  np.asarray(m_l["loss"]))
    assert st_l.bits.shape == (4, len(LEAF_SIZES))
    np.testing.assert_array_equal(np.asarray(st_l.bits), 4)


LW = LayerwiseConfig(bits=(4, 2, 3, 1), periods=(1, 2, 3, 1), taus=1e-6)
COMPOSITIONS = [
    dict(),
    dict(censor=CensorConfig(tau=1e-3, xi=0.9)),
    dict(staleness=1, participation=0.75),
]


@pytest.mark.parametrize("extra", COMPOSITIONS,
                         ids=["plain", "censor", "stale_partial"])
@pytest.mark.parametrize("pack_wire", [False, True])
def test_layerwise_parity_jnp_vs_pallas(extra, pack_wire):
    """Per-leaf bits x periods x taus composed with censoring / staleness /
    participation: wire_impl='pallas' is bit-identical to 'jnp' through
    whole train steps (the shared uniform-draw convention extends to the
    per-element-levels kernel path)."""
    tr_j, st_j, batch = _setup(layerwise=LW, pack_wire=pack_wire,
                               wire_impl="jnp", **extra)
    tr_p, st_p, _ = _setup(layerwise=LW, pack_wire=pack_wire,
                           wire_impl="pallas", **extra)
    st_j, m_j = _run(tr_j, st_j, batch)
    st_p, m_p = _run(tr_p, st_p, batch)
    _assert_states_equal(st_j, st_p)
    np.testing.assert_array_equal(np.asarray(m_j["loss"]),
                                  np.asarray(m_p["loss"]))
    np.testing.assert_array_equal(np.asarray(m_j["wire_bits_per_round"]),
                                  np.asarray(m_p["wire_bits_per_round"]))


def test_layerwise_periods_hold_last_hat():
    """A leaf with period P is transmitted only on rounds where
    step % P == 0; in between, both endpoints hold its last hat (and the
    round's wire bill drops by the silent leaf's payload)."""
    # wa (index 2 in leaf order) transmits on even steps only
    tr, st, batch = _setup(layerwise=LayerwiseConfig(periods=(1, 1, 2, 1)))
    step = jax.jit(tr.make_train_step())
    st1, m1 = step(st, batch)     # round 0: all leaves due
    st2, m2 = step(st1, batch)    # round 1: wa silent
    st3, m3 = step(st2, batch)    # round 2: all leaves due again
    np.testing.assert_array_equal(np.asarray(st2.theta_hat["wa"]),
                                  np.asarray(st1.theta_hat["wa"]))
    assert np.any(np.asarray(st3.theta_hat["wa"])
                  != np.asarray(st2.theta_hat["wa"]))
    # silent leaf also keeps its committed radius and bits rows
    np.testing.assert_array_equal(np.asarray(st2.radius[:, 2]),
                                  np.asarray(st1.radius[:, 2]))
    assert float(m2["wire_bits_per_round"]) < float(m1["wire_bits_per_round"])
    assert float(m3["wire_bits_per_round"]) == float(
        m1["wire_bits_per_round"])


def test_layerwise_wire_accounting_closed_form():
    """With every leaf due and nothing censored, the layerwise metric equals
    the closed form: per phase, every directed edge carries L 1-bit flags
    and each worker's transmission bills deg(w) * sum_l (8 * bytes_l +
    header_bits()) on the mixed pack format (packed_len at <= 4 bits)."""
    tr, st, batch = _setup(layerwise=LayerwiseConfig())
    _, m = _run(tr, st, batch, steps=1)
    n_edges, n_leaves = 3, len(LEAF_SIZES)          # chain of 4 workers
    deg = (1, 2, 2, 1)
    per_leaf = [8 * packed_len(n) + header_bits() for n in LEAF_SIZES]
    expect = (2 * (2 * n_edges * n_leaves * FLAG_BITS)   # 2 g-s phases
              + sum(deg) * sum(per_leaf))
    assert float(m["wire_bits_per_round"]) == float(expect)


def test_allocate_bits_contract():
    """Controller invariants: floor at min_bits, range respected, budget
    conserved, and strictly better-scored leaves never get fewer bits."""
    sizes = np.asarray(LEAF_SIZES, np.float32)
    scores = jnp.asarray([0.5, 0.0, 3.0, 1.0])
    for budget in (0, 39, 100, 150, 10_000):
        b = allocate_bits(scores, sizes, budget, 1, 8)
        assert b.shape == scores.shape and b.dtype == jnp.int32
        assert int(jnp.min(b)) >= 1 and int(jnp.max(b)) <= 8
        spend = float(jnp.sum(b * sizes))
        assert spend <= max(budget, 1 * float(sizes.sum())) + 1e-6
    b = allocate_bits(scores, sizes, 150, 1, 8)
    order = np.argsort(-np.asarray(scores))
    bs = np.asarray(b)[order]
    assert all(bs[i] >= bs[i + 1] or sizes[order][i] > sizes[order][i + 1]
               for i in range(len(bs) - 1))
    # batched scores allocate row-wise
    b2 = allocate_bits(jnp.stack([scores, scores[::-1]]), sizes, 150, 1, 8)
    assert b2.shape == (2, 4)
    np.testing.assert_array_equal(np.asarray(b2[0]), np.asarray(b))


def test_bit_budget_conservation_in_trainer():
    """With budget_bits set, every worker's committed per-leaf widths spend
    at most max(budget, min_bits * d) payload bits per transmission."""
    budget = 100
    tr, st, batch = _setup(layerwise=LayerwiseConfig(budget_bits=budget))
    st, m = _run(tr, st, batch)
    sizes = np.asarray(LEAF_SIZES, np.float32)
    bits = np.asarray(st.bits)
    assert bits.shape == (4, len(LEAF_SIZES))
    assert bits.min() >= 1 and bits.max() <= 8
    spend = (bits * sizes).sum(axis=1)
    assert np.all(spend <= max(budget, sizes.sum())), spend
    assert np.isfinite(float(m["loss"]))


def test_layerwise_adapt_bits_eq11():
    """adapt_bits=True applies the eq. 11 growth rule per leaf: committed
    widths stay in [min_bits, max_bits] with per-leaf (W, L) state."""
    lw = LayerwiseConfig(adapt_bits=True, max_bits=6)
    tr, st, batch = _setup(layerwise=lw)
    st, m = _run(tr, st, batch)
    bits = np.asarray(st.bits)
    assert bits.shape == (4, len(LEAF_SIZES))
    assert bits.min() >= lw.min_bits and bits.max() <= lw.max_bits
    assert np.isfinite(float(m["loss"]))
