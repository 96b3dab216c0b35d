"""The sharded round against the co-located round, with the neighbor state
held by owner (`DistState.hat_nbr`/`lam_nbr`, (W * C, ...) sharded like
theta): each chip decodes and dual-updates its own worker's slots.

Each case runs in a subprocess with 4 virtual CPU devices (the main pytest
process must keep seeing 1 device): whisper-tiny at smoke widths, W=4, one
worker per device (`jit_train_step`) against `make_train_step` on the same
data.  Each of 3 rounds starts both steps from the co-located state, so a
round compares one round's arithmetic: chained, the partitioned local
solve's round-off compounds through the stochastic rounding and Adam's
sign-normalised steps, whatever the neighbor-state layout.

Tolerances are the sharded-vs-co-located ones of
`test_unsharded_reference_vs_jit_train_step_censored` (state rtol 2e-2,
atol 1e-4; skip_rate and billed wire bits identical) and
`test_dist_matches_single_process_reference` (loss within 2e-2).  The
directed-edge readers (`hat_edge`/`lam_edge`, read by the benchmark's
correctness check) must give (2E, ...) rows in `edge_index` order, equal
to the co-located run's, with row d = dst(d)'s copy of src(d)'s hat and
the two rows of an edge holding the same dual.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CODE = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.censor import CensorConfig
    from repro.core.gadmm import GADMMConfig
    from repro.core.quantizer import QuantizerConfig
    from repro.dist.qgadmm import DistConfig, QGADMMTrainer, init_state
    from repro.launch import train as launch
    from repro.models import registry

    topology, wire, stale = {topology!r}, {wire!r}, {stale}
    cfg = registry.get_config("whisper-tiny", smoke=True)
    model = registry.get_model(cfg)
    gadmm = GADMMConfig(rho=1.0, quantize=wire != "f32",
                        qcfg=QuantizerConfig(bits=8 if wire == "q8" else 4),
                        alpha=0.01)
    dcfg = DistConfig(
        num_workers=4, gadmm=gadmm, local_iters=1, local_lr=1e-3,
        topology=topology, staleness=stale,
        censor=CensorConfig(tau=0.05, xi=0.9) if wire == "censor" else None)
    assert dcfg.pack_wire == (wire in ("q4", "censor"))
    mesh = launch.worker_mesh(np.asarray(jax.devices()), 4)
    assert dict(mesh.shape) == {{"worker": 4, "fsdp": 1, "model": 1}}
    tr = QGADMMTrainer(model, cfg, dcfg, mesh)
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    batch = {{"tokens": jax.random.randint(k[0], (4, 2, 16), 0, cfg.vocab),
              "labels": jax.random.randint(k[1], (4, 2, 16), 0, cfg.vocab),
              "frames": jax.random.normal(
                  k[2], (4, 2, cfg.encoder_frames, cfg.d_model))}}
    st_u = init_state(lambda kk: model.init(kk, cfg), jax.random.PRNGKey(0),
                      dcfg)
    st_s, b = tr.place(st_u, batch)
    step_s = tr.jit_train_step(st_s, b)
    step_u = jax.jit(tr.make_train_step())
    c_ports, e = tr.topo.num_ports, tr.eidx
    f32 = lambda a: np.asarray(jnp.asarray(a, jnp.float32))
    for it in range(3):
        st_s, m_s = step_s(tr.place(st_u, batch)[0], b)
        st_u, m_u = step_u(st_u, batch)
        assert float(m_s["skip_rate"]) == float(m_u["skip_rate"])
        assert (float(m_s["wire_bits_per_round"])
                == float(m_u["wire_bits_per_round"]))
        assert abs(float(m_s["loss"]) - float(m_u["loss"])) < 2e-2
        np.testing.assert_allclose(float(m_s["consensus_resid"]),
                                   float(m_u["consensus_resid"]),
                                   rtol=2e-2, atol=1e-4)
        for f in st_s._fields:
            for a, c in zip(jax.tree.leaves(getattr(st_s, f)),
                            jax.tree.leaves(getattr(st_u, f))):
                np.testing.assert_allclose(
                    f32(a), f32(c), rtol=2e-2, atol=1e-4,
                    err_msg=f"round {{it}} field {{f}}")
        # the owner slots stay on their worker's device: C rows each
        for a in jax.tree.leaves((st_s.hat_nbr, st_s.lam_nbr)):
            assert a.shape[0] == 4 * c_ports, a.shape
            assert a.sharding.spec[0] == "worker", a.sharding
        # the directed-edge readers: (2E, ...) rows in edge_index order
        for name in ("hat_edge", "lam_edge"):
            slots = name.replace("edge", "nbr")
            for a, c, sl, own in zip(
                    jax.tree.leaves(getattr(st_s, name)),
                    jax.tree.leaves(getattr(st_u, name)),
                    jax.tree.leaves(getattr(st_u, slots)),
                    jax.tree.leaves(st_u.theta)):
                assert a.shape == (e.num_directed,) + own.shape[1:]
                np.testing.assert_allclose(
                    f32(a), f32(c), rtol=2e-2, atol=1e-4,
                    err_msg=f"round {{it}} {{name}}")
                np.testing.assert_array_equal(
                    f32(c), f32(sl)[e.dst * c_ports + e.color])
        # row d is dst(d)'s copy of src(d)'s hat; at S=0 the co-located
        # copy is the sender's own hat, bitwise
        if stale == 0:
            for a, h in zip(jax.tree.leaves(st_u.hat_edge),
                            jax.tree.leaves(st_u.theta_hat)):
                np.testing.assert_array_equal(f32(a), f32(h)[e.src])
        # both rows of an edge hold the same dual (to round-off)
        rev = [int(np.flatnonzero((e.src == t) & (e.dst == s))[0])
               for s, t in zip(e.src, e.dst)]
        for a in jax.tree.leaves(st_u.lam_edge):
            np.testing.assert_allclose(f32(a)[rev], f32(a), rtol=1e-3,
                                       atol=1e-8)
    print("OK")
"""

# 8-bit runs at S=1: at S=0 the tails' first Adam step, sign-normalised,
# turns the round-off of near-zero gradients into +-lr in a few elements
# of round 0 (whisper-tiny smoke, seed 1: two elements of worker 1, which
# a level flip then carries into its hat), the same elements with and
# without the owner-slot layout.  At S=1 the tails solve against S-stale
# hats and the comparison holds at the reference tolerance.
CASES = [
    ("chain", "q4", 0), ("chain", "q8", 1), ("chain", "f32", 0),
    ("chain", "censor", 0), ("chain", "q4", 1),
    ("ring", "q4", 0), ("ring", "f32", 0), ("ring", "q8", 1),
    ("ring", "censor", 1),
    ("star", "q4", 0), ("star", "q8", 1), ("star", "censor", 0),
    ("star", "f32", 1),
]


@pytest.mark.parametrize("topology,wire,stale", CASES,
                         ids=[f"{t}-{w}-s{s}" for t, w, s in CASES])
def test_sharded_owner_slots_match_colocated(topology, wire, stale):
    """Sharded W=4 round == co-located round, 3 rounds, per topology, wire
    (q4: packed 4-bit, q8, f32, censor: packed 4-bit censored) and
    staleness S; the edge readers answer in edge_index order."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    code = textwrap.dedent(CODE).format(topology=topology, wire=wire,
                                        stale=stale)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "OK" in r.stdout
