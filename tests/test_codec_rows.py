"""Each Gauss-Seidel phase draws and codes only the rows it commits.

The shared uniform draw is row-addressed: row w of `jax.random.uniform(key,
(W, D_pad))` is regenerated from its own threefry counters in lane-dense
(.., 128) tiles (`qgadmm.row_uniform`), so `phase_compute(..., rows=group)`
runs the radii, flatten, draw, quantize, decode and commits on the group
alone.  Checked here on the CPU, bitwise:

  * the row draw against `jax.random.uniform(key, (W, D_pad))[rows]`, and
    its counters' carry into the high word;
  * one phase with rows=group against the all-row codec from the same
    inputs, over the wire modes: the group's wire, hats, radius, bits,
    `sent` and billed bits; every other row as it came in, and a payload
    that sends nothing;
  * three rounds of the co-located step (plain, overlap, staleness 1) on
    a fixed 8-bit wire (widths XLA sees as constants) and an adaptive
    packed one (traced widths) against the same step coding every row in
    both phases, with every receiver's hat bitwise its sender's;
  * `QGADMMTrainer.codec_rows` against the rows the traced round codes;
  * the worker-sharded round (4 virtual devices, a subprocess like
    tests/test_dist.py), whose devices draw their own slabs inside the
    codec's shard_map, against the global draw the partitioner splits.
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
from repro.core.quantizer import LayerwiseConfig, QuantizerConfig
from repro.dist import qgadmm
from repro.dist.qgadmm import DistConfig, QGADMMTrainer, init_state

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


# ----------------------------------------------------------- (a) the draw --
@pytest.mark.parametrize("d_pad", [1024, 1_000_003])
@pytest.mark.parametrize("w,rows", [(2, (0,)), (2, (1,)), (4, (0, 2)),
                                    (4, (1, 3)), (4, (0, 1, 2, 3))])
def test_row_draw_is_the_shared_draw(w, rows, d_pad):
    key = jax.random.PRNGKey(2_718_281_829)
    full = np.asarray(jax.random.uniform(key, (w, d_pad), jnp.float32))
    u = np.asarray(jax.jit(qgadmm.row_uniform, static_argnums=(1, 2, 3))(
        key, rows, w, d_pad))
    assert u.shape == (len(rows), -(-d_pad // 128), 128)
    got = u.reshape(len(rows), -1)[:, :d_pad]
    assert np.array_equal(got.view(np.uint32),
                          full[list(rows)].view(np.uint32))


def test_row_draw_counters_carry_into_the_high_word():
    """A row starting just below 2**32 crosses into the high word: its
    draw is the hash of the 64-bit counters start + j."""
    key = jax.random.PRNGKey(5)
    start = 2 ** 32 - 200
    hi, lo = qgadmm._split_words([start])
    u = np.asarray(qgadmm._uniform_at(key, hi, lo, 300)).reshape(-1)[:300]
    c = start + np.arange(300, dtype=np.uint64)
    b1, b2 = jax.extend.random.threefry2x32_p.bind(
        key[0], key[1], jnp.asarray((c >> np.uint64(32)).astype(np.uint32)),
        jnp.asarray((c & np.uint64(2 ** 32 - 1)).astype(np.uint32)))
    bits = (np.asarray(b1) ^ np.asarray(b2)) >> np.uint32(9)
    want = (bits | np.uint32(0x3F800000)).view(np.float32) - np.float32(1)
    assert np.array_equal(u.view(np.uint32), want.view(np.uint32))
    assert (c >> np.uint64(32)).min() == 0 and (c >> np.uint64(32)).max() == 1


# ------------------------------------------------------- (b) one phase ----
_MODES = {
    "q8": dict(),
    "q4-packed": dict(bits=4, pack_wire=True),
    "per-tensor": dict(radius_mode="per_tensor"),
    "adaptive": dict(adapt=True),
    "layerwise": dict(layerwise=LayerwiseConfig(
        bits=(4, 6), periods=(1, 2), taus=1e-6)),
    "censor": dict(censor=CensorConfig(tau=0.3, xi=0.95)),
    "participation": dict(participation=True),
    "f32": dict(quantize=False),
}


def _setup(w, bits=8, adapt=False, quantize=True, **dist):
    rng = np.random.default_rng(w)
    x = rng.normal(size=(w, 16, 6))
    y = x @ rng.normal(size=(6, 3))
    batch = {"x": jnp.asarray(x, jnp.float32),
             "y": jnp.asarray(y, jnp.float32)}
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("worker", "fsdp", "model"))
    dcfg = DistConfig(
        num_workers=w, topology="chain",
        gadmm=GADMMConfig(rho=0.5, quantize=quantize, alpha=0.1,
                          qcfg=QuantizerConfig(
                              bits=bits, adapt_bits=adapt,
                              max_bits=4 if dist.get("pack_wire") else 8)),
        local_iters=2, local_lr=5e-2, **dist)
    tr = QGADMMTrainer(_Affine, None, dcfg, mesh)
    st0 = init_state(lambda k: _Affine.init(k, None), jax.random.PRNGKey(0),
                     dcfg)
    return tr, st0, batch


def _all_rows(tr):
    """The same trainer's step with every phase coding every row."""
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


def _billed(tr, theta, pl):
    leaf = pl.get("leaf_sent")
    return np.asarray(tr.wire_bits_per_round(
        theta, [pl["sent"]],
        None if leaf is None else [(leaf, pl["bits"])]))


@pytest.mark.parametrize("mode", list(_MODES))
def test_phase_codes_only_its_rows(mode):
    opts = dict(_MODES[mode])
    part = opts.pop("participation", False)
    w = 4
    tr, st0, batch = _setup(w, **opts)
    step = _all_rows(tr)
    state = st0
    for _ in range(2):               # nonzero hats, duals and moments
        state, _ = step(state, batch)
    del tr._groups
    st = _tup(state)
    active = np.ones((w,), bool)
    pw = None
    if part:
        active = np.random.default_rng(3).random(w) < 0.5
        pw = tr.pmask * jnp.asarray(np.random.default_rng(4).uniform(
            0.5, 2.0, tr.pmask.shape), jnp.float32)
    key = jax.random.PRNGKey(11)
    head = np.asarray(tr.topo.head_mask)
    for group in (head, ~head):
        rows = np.flatnonzero(group)
        sub = jax.jit(lambda st, a: tr.phase_compute(
            st, batch, a, key, state.step, port_weights=pw, rows=rows))
        full = jax.jit(lambda st, a: tr.phase_compute(
            st, batch, a, key, state.step, port_weights=pw))
        st_s, pl_s, _ = sub(st, jnp.asarray(active))
        st_f, pl_f, _ = full(st, jnp.asarray(active & group))
        assert _equal(st_s, st_f)
        assert set(pl_s) == set(pl_f)
        for k in pl_f:
            assert np.array_equal(np.asarray(pl_s[k])[group],
                                  np.asarray(pl_f[k])[group]), k
        assert np.array_equal(_billed(tr, st_s[0], pl_s),
                              _billed(tr, st_f[0], pl_f))
        # the rows left out send nothing and keep their state
        assert not np.asarray(pl_s["sent"])[~group].any()
        assert not np.asarray(pl_s["wire"])[~group].any()
        for k, i in (("radius", 4), ("bits", 5)):
            if k in pl_s:
                assert np.array_equal(np.asarray(pl_s[k])[~group],
                                      np.asarray(st[i])[~group]), k
        for new, old in zip(st_s, st):
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
                if a.shape[0] == w:
                    assert np.array_equal(np.asarray(a)[~group],
                                          np.asarray(b)[~group])
        if (active & group).any() and mode != "censor":
            assert np.asarray(pl_s["sent"])[active & group].all()


# --------------------------------------------- (c) three co-located rounds --
def _hats_in_sync(tr, state):
    """Every slot holds its partner's hat bitwise: the sender's committed
    hat, or with staleness S its S-stale snapshot `hat_lag`."""
    own = state.hat_lag if tr.dcfg.staleness else state.theta_hat
    port = tr.topo.port
    c = port.shape[1]
    for w in range(port.shape[0]):
        for k in range(c):
            if port[w, k] < 0:
                continue
            for slot, h in zip(jax.tree.leaves(state.hat_nbr),
                               jax.tree.leaves(own)):
                if not np.array_equal(np.asarray(slot)[w * c + k],
                                      np.asarray(h)[port[w, k]]):
                    return False
    return True


@pytest.mark.parametrize("wire", [dict(bits=8),
                                  dict(bits=2, adapt=True, pack_wire=True)],
                         ids=["q8", "adaptive-packed"])
@pytest.mark.parametrize("route", [{}, {"overlap": True}, {"staleness": 1}],
                         ids=["plain", "overlap", "stale1"])
def test_colocated_rounds_match_the_all_row_codec(route, wire):
    opts = dict(**wire, **route)
    tr, st0, batch = _setup(4, **opts)
    step = jax.jit(tr.make_train_step())
    ref_tr, _, _ = _setup(4, **opts)
    ref = _all_rows(ref_tr)
    a = b = st0
    for _ in range(3):
        a, ma = step(a, batch)
        b, mb = ref(b, batch)
        assert _equal(a, b)
        assert _equal(ma, mb)
        assert _hats_in_sync(tr, a)


# ----------------------------------------------------- (d) the counter ----
def _count_rows(tr):
    """Wrap tr._quantize_all to count the rows the traced rounds code."""
    counts, inner = [], tr._quantize_all

    def counting(theta, *args, **kw):
        counts.append(jax.tree.leaves(theta)[0].shape[0])
        return inner(theta, *args, **kw)

    tr._quantize_all = counting
    return counts


@pytest.mark.parametrize("w,route", [(2, {}), (4, {"overlap": True}),
                                     (4, {"staleness": 1}),
                                     (4, {"mode": "jacobi"})],
                         ids=["gs-w2", "overlap-w4", "stale1-w4",
                              "jacobi-w4"])
def test_codec_rows_colocated(w, route):
    tr, st0, batch = _setup(w, **route)
    counts = _count_rows(tr)
    jax.make_jaxpr(tr.make_train_step())(st0, batch)
    assert sum(counts) == tr.codec_rows(sharded=False) == w


# ------------------------------------------------- the sharded round ----
def test_sharded_draw_matches_the_global_draw():
    """One worker per device (W=4) and two devices per worker (W=2, so
    each draws the second half of its row from counter D_pad / 2): three
    rounds of jit_train_step bitwise the same round whose codec takes the
    global (W, D_pad) draw split by the partitioner; codec_rows counts
    both phases' rows."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core.gadmm import GADMMConfig
        from repro.core.quantizer import QuantizerConfig
        from repro.dist.qgadmm import DistConfig, QGADMMTrainer, init_state

        class M:
            init = staticmethod(lambda k, c: {
                "w": jax.random.normal(k, (6, 3)) * 0.1,
                "b": jnp.zeros((3,))})
            loss_fn = staticmethod(lambda p, b, c: jnp.mean(
                (b["x"] @ p["w"] + p["b"] - b["y"]) ** 2))

        class GlobalDraw(QGADMMTrainer):
            # the codec as it drew before rows: one uniform over the
            # padded (W, D_pad) buffer, sharded by the partitioner
            def _qdq_sharded(self, theta_f, hat_f, key, radius, bits,
                             seg=None):
                assert seg is None
                u = jax.random.uniform(key, theta_f.shape, jnp.float32)
                bspec = P("worker", ("fsdp", "model"))

                def body(th, h, uu, rr, bb):
                    q, hh = self._qdq_row(th[0], h[0], uu[0], rr[0], bb[0])
                    return q[None], hh[None]

                return jax.shard_map(
                    body, mesh=self.mesh,
                    in_specs=(bspec, bspec, bspec, P("worker"), P("worker")),
                    out_specs=(bspec, bspec), check_vma=False)(
                        theta_f, hat_f, u, radius, bits)

        devs = np.asarray(jax.devices())
        for w, grid, bits in ((4, (4, 1, 1), 4), (2, (2, 2, 1), 8)):
            mesh = Mesh(devs.reshape(grid), ("worker", "fsdp", "model"))
            dcfg = DistConfig(
                num_workers=w, pack_wire=bits == 4, local_iters=2,
                local_lr=5e-2,
                gadmm=GADMMConfig(rho=0.5, alpha=0.1,
                                  qcfg=QuantizerConfig(bits=bits)))
            rng = np.random.default_rng(w)
            x = rng.normal(size=(w, 16, 6)).astype(np.float32)
            batch = {"x": jnp.asarray(x),
                     "y": jnp.asarray(x @ rng.normal(size=(6, 3))
                                      .astype(np.float32))}
            out = []
            for cls in (QGADMMTrainer, GlobalDraw):
                tr = cls(M, None, dcfg, mesh)
                st = init_state(lambda k: M.init(k, None),
                                jax.random.PRNGKey(0), dcfg)
                st, b = tr.place(st, batch)
                step = tr.jit_train_step(st, b)
                states = []
                for _ in range(3):
                    st, m = step(st, b)
                    states.append(jax.device_get((st, m)))
                out.append(states)
            same = all(
                np.array_equal(np.asarray(p), np.asarray(q))
                for s, t in zip(*out)
                for p, q in zip(jax.tree.leaves(s), jax.tree.leaves(t)))
            moved = not np.array_equal(out[0][-1][0].theta_hat["w"],
                                       np.zeros_like(
                                           out[0][-1][0].theta_hat["w"]))
            print(w, same, moved, tr.codec_rows(sharded=True))
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=400, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    assert r.stdout.split("\n")[:2] == ["4 True True 8",
                                        "2 True True 4"], r.stdout
