"""The co-located round solves each Gauss-Seidel group alone.

`QGADMMTrainer.phase_compute(..., rows=group)` runs the local solve on a
static worker subset and commits only those rows; where all workers share
one device the round passes the heads in phase 1 and the tails in phase 2
(`_groups`), so each worker runs one local solve a round instead of two.
Checked here on the CPU, bitwise:

  * one phase: rows=group against the all-row phase_compute from the same
    inputs (chain W=2 and W=4, star W=4; 8-bit and f32 wire; participation
    0.5 and censoring), and every row outside the group as it came in;
  * the same at the whisper-tiny and mamba2 smoke widths (chain, 8-bit):
    bitwise for groups of two rows (W=4).  A group of one row (W=2) has
    its worker axis dropped by XLA, which then fuses the Adam update of
    the leaves outside the layer stack (final norms, unembedding)
    differently: the moments, the hats and the wire stay bitwise, theta
    moves by a few roundings of the update there, f0 by a few ulps;
  * three rounds of the co-located step (plain, overlap, staleness 1)
    against the same step with every phase solving all rows, and the
    round's loss against each worker's start-of-round data loss;
  * `QGADMMTrainer.local_solves` against the solves the traced round runs:
    W on the co-located Gauss-Seidel and Jacobi rounds, 2W on the
    worker-sharded round (4 virtual devices, in a subprocess like
    tests/test_dist.py).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core.censor import CensorConfig
from repro.core.gadmm import GADMMConfig
from repro.core.quantizer import QuantizerConfig
from repro.dist.qgadmm import DistConfig, QGADMMTrainer, init_state
from repro.models import registry

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class _Affine:
    """Two leaves of different shapes, so the wire spans several leaves."""

    @staticmethod
    def init(key, cfg):
        return {"w": jax.random.normal(key, (6, 3)) * 0.1,
                "b": jnp.zeros((3,))}

    @staticmethod
    def loss_fn(params, batch, cfg):
        pred = batch["x"] @ params["w"] + params["b"]
        return jnp.mean((pred - batch["y"]) ** 2)


def _setup(topology, w, quantize, censor=False, **dist):
    rng = np.random.default_rng(w)
    x = rng.normal(size=(w, 16, 6))
    y = x @ rng.normal(size=(6, 3))
    batch = {"x": jnp.asarray(x, jnp.float32),
             "y": jnp.asarray(y, jnp.float32)}
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("worker", "fsdp", "model"))
    dcfg = DistConfig(
        num_workers=w, topology=topology,
        censor=CensorConfig(tau=0.3, xi=0.95) if censor else None,
        gadmm=GADMMConfig(rho=0.5, quantize=quantize,
                          qcfg=QuantizerConfig(bits=8), alpha=0.1),
        local_iters=2, local_lr=5e-2, **dist)
    tr = QGADMMTrainer(_Affine, None, dcfg, mesh)
    st0 = init_state(lambda k: _Affine.init(k, None), jax.random.PRNGKey(0),
                     dcfg)
    return tr, st0, batch


def _all_rows(tr):
    """The same trainer's step with every phase solving every row."""
    tr._groups = lambda sharded: (None, None)
    return jax.jit(tr.make_train_step())


def _tup(state):
    return (state.theta, state.theta_hat, state.hat_nbr, state.lam_nbr,
            state.radius, state.bits, state.opt_mu, state.opt_nu,
            state.opt_t)


def _equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


@pytest.mark.parametrize("variant", ["full", "participation", "censor"])
@pytest.mark.parametrize("quantize", [True, False], ids=["q8", "f32"])
@pytest.mark.parametrize("topology,w", [("chain", 2), ("chain", 4),
                                        ("star", 4)])
def test_row_subset_matches_all_rows(topology, w, quantize, variant):
    tr, st0, batch = _setup(topology, w, quantize,
                            censor=variant == "censor")
    # two all-row rounds, so the duals, hats and Adam moments are nonzero
    step = _all_rows(tr)
    state = st0
    for _ in range(2):
        state, _ = step(state, batch)
    st = _tup(state)
    rng = np.random.default_rng(7)
    part = np.ones((w,), bool)
    pw = None
    if variant == "participation":
        part = rng.random(w) < 0.5
        pw = jnp.asarray(rng.uniform(0.5, 2.0, tr.pmask.shape),
                         jnp.float32) * tr.pmask
    key = jax.random.PRNGKey(3)
    head = np.asarray(tr.topo.head_mask)
    for group in (head, ~head):
        rows = np.flatnonzero(group)

        @jax.jit
        def sub(st, active):
            return tr.phase_compute(st, batch, active, key, state.step,
                                    port_weights=pw, rows=rows)

        @jax.jit
        def full(st, active):
            return tr.phase_compute(st, batch, active, key, state.step,
                                    port_weights=pw)

        # rows cuts `active` to the group itself
        st_s, pl_s, f0_s = sub(st, jnp.asarray(part))
        st_f, pl_f, f0_f = full(st, jnp.asarray(part & group))
        assert _equal(st_s, st_f)
        assert _equal(pl_s, pl_f)
        assert np.array_equal(np.asarray(f0_s)[rows], np.asarray(f0_f)[rows])
        assert not np.asarray(f0_s)[~group].any()
        assert not np.asarray(pl_s["sent"])[~group].any()
        # every row outside the group is its input, bit for bit
        for i, (new, old) in enumerate(zip(st_s, st)):
            if i in (2, 3):            # the edge slabs: no phase writes them
                assert _equal(new, old)
                continue
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
                assert np.array_equal(np.asarray(a)[~group],
                                      np.asarray(b)[~group])
        # the group's active rows did move
        moved = part & group
        if moved.any():
            assert not np.array_equal(np.asarray(st_s[0]["w"])[moved],
                                      np.asarray(st[0]["w"])[moved])


def _smoke_setup(arch, w):
    """The model at its smoke widths, W workers on a chain, the benchmark's
    rho, learning rate and 8-bit wire, after two all-row rounds."""
    cfg = registry.get_config(arch, smoke=True)
    model = registry.get_model(cfg)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("worker", "fsdp", "model"))
    dcfg = DistConfig(num_workers=w,
                      gadmm=GADMMConfig(rho=1.0, quantize=True,
                                        qcfg=QuantizerConfig(bits=8),
                                        alpha=0.01),
                      local_iters=1, local_lr=1e-3)
    tr = QGADMMTrainer(model, cfg, dcfg, mesh)
    rng = np.random.default_rng(w)
    batch = {k: jnp.asarray(rng.integers(0, cfg.vocab, (w, 2, 16)),
                            jnp.int32) for k in ("tokens", "labels")}
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(rng.normal(
            size=(w, 2, cfg.encoder_frames, cfg.d_model)), jnp.float32)
    state = init_state(lambda k: model.init(k, cfg), jax.random.PRNGKey(0),
                       dcfg)
    step = _all_rows(tr)
    for _ in range(2):
        state, _ = step(state, batch)
    del tr._groups
    return tr, state, batch


@pytest.mark.parametrize("w", [2, 4])
@pytest.mark.parametrize("arch", ["whisper-tiny", "mamba2-2.7b"])
def test_row_subset_matches_all_rows_smoke_models(arch, w):
    tr, state, batch = _smoke_setup(arch, w)
    st = _tup(state)
    key = jax.random.PRNGKey(3)
    head = np.asarray(tr.topo.head_mask)
    for group in (head, ~head):
        rows = np.flatnonzero(group)
        sub = jax.jit(lambda st, a: tr.phase_compute(
            st, batch, a, key, state.step, rows=rows))
        full = jax.jit(lambda st, a: tr.phase_compute(
            st, batch, a, key, state.step))
        st_s, pl_s, f0_s = sub(st, jnp.ones((w,), bool))
        st_f, pl_f, f0_f = full(st, jnp.asarray(group))
        # hats, edge slabs, radius, bits, Adam moments and steps, the wire
        assert _equal(st_s[1:], st_f[1:])
        assert _equal(pl_s, pl_f)
        for new, old in zip(jax.tree.leaves(st_s[0]),
                            jax.tree.leaves(st[0])):
            assert np.array_equal(np.asarray(new)[~group],
                                  np.asarray(old)[~group])
        if w == 4:
            assert _equal(st_s[0], st_f[0])
            assert np.array_equal(np.asarray(f0_s)[rows],
                                  np.asarray(f0_f)[rows])
            continue
        # one-row group: theta within four roundings of |theta| + |update|
        for a, b, o in zip(jax.tree.leaves(st_s[0]), jax.tree.leaves(st_f[0]),
                           jax.tree.leaves(st[0])):
            a, b, o = (np.asarray(x, np.float64)[rows] for x in (a, b, o))
            bound = 4 * 2.0 ** -23 * (np.abs(o) + np.abs(b - o))
            assert np.all(np.abs(a - b) <= bound)
        np.testing.assert_array_max_ulp(np.asarray(f0_s)[rows],
                                        np.asarray(f0_f)[rows], maxulp=4)


@pytest.mark.parametrize("route", [{}, {"overlap": True}, {"staleness": 1}],
                         ids=["plain", "overlap", "stale1"])
def test_colocated_round_matches_all_row_round(route):
    tr, st0, batch = _setup("chain", 4, True, censor=True, **route)
    step = jax.jit(tr.make_train_step())
    ref_tr, _, _ = _setup("chain", 4, True, censor=True, **route)
    ref = _all_rows(ref_tr)
    a = b = st0
    for _ in range(3):
        theta0 = a.theta
        a, ma = step(a, batch)
        b, mb = ref(b, batch)
        assert _equal(a, b)
        assert _equal(ma, mb)
        # the round's loss: each worker's data loss at the start of it
        direct = np.mean([float(_Affine.loss_fn(
            jax.tree.map(lambda l: l[i], theta0),
            jax.tree.map(lambda l: l[i], batch), None)) for i in range(4)])
        np.testing.assert_allclose(float(ma["loss"]), direct, rtol=1e-6)
    assert tr.local_solves(sharded=False) == 4
    assert ref_tr.local_solves(sharded=False) == 8


def _count_solves(tr):
    """Wrap tr.phase_compute to count the worker solves the traced rounds
    run; returns the list the counts go to."""
    counts, inner = [], tr.phase_compute

    def counting(*args, rows=None, **kw):
        counts.append(tr.dcfg.num_workers if rows is None else len(rows))
        return inner(*args, rows=rows, **kw)

    tr.phase_compute = counting
    return counts


@pytest.mark.parametrize("mode,expect", [("gauss-seidel", 2), ("jacobi", 2)])
def test_local_solves_colocated(mode, expect):
    tr, st0, batch = _setup("chain", 2, True, mode=mode)
    counts = _count_solves(tr)
    jax.make_jaxpr(tr.make_train_step())(st0, batch)
    assert sum(counts) == tr.local_solves(sharded=False) == expect


def test_local_solves_sharded():
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch import train as launch
        from repro.dist.qgadmm import DistConfig, QGADMMTrainer, init_state
        from repro.core.gadmm import GADMMConfig

        class M:
            init = staticmethod(lambda k, c: {"w": jnp.zeros((6,))})
            loss_fn = staticmethod(
                lambda p, b, c: jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2))

        for mode, staleness in (("gauss-seidel", 0), ("gauss-seidel", 1),
                                ("jacobi", 0)):
            dcfg = DistConfig(num_workers=4, mode=mode, staleness=staleness,
                              gadmm=GADMMConfig(rho=0.5, alpha=0.1))
            mesh = launch.worker_mesh(np.asarray(jax.devices()), 4)
            tr = QGADMMTrainer(M, None, dcfg, mesh)
            st = init_state(lambda k: M.init(k, None), jax.random.PRNGKey(0),
                            dcfg)
            b = {"x": jnp.ones((4, 8, 6)), "y": jnp.ones((4, 8))}
            st, b = tr.place(st, b)
            counts, inner = [], tr.phase_compute

            def counting(*args, rows=None, **kw):
                counts.append(4 if rows is None else len(rows))
                return inner(*args, rows=rows, **kw)

            tr.phase_compute = counting
            tr.jit_train_step(st, b).lower(st, b)
            print(mode, staleness, sum(counts), tr.local_solves(sharded=True))
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    assert r.stdout.split("\n")[:3] == ["gauss-seidel 0 8 8",
                                        "gauss-seidel 1 8 8",
                                        "jacobi 0 4 4"], r.stdout
