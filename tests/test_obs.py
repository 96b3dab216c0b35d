"""Observability tier (ISSUE 9): schema round-trips, telemetry parity,
trace accounting, live invariants, and the committed BENCH_* shapes.

The keystone contract: telemetry is a pure READ of the step's state —
running the trainer with telemetry on (metrics appended to a MetricsLog
and drained in windows) yields a state stream BIT-IDENTICAL to telemetry
off.  Asserted with array_equal on every DistState field (bf16 viewed as
uint8), never allclose.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core.censor import FLAG_BITS, CensorConfig
from repro.core.gadmm import GADMMConfig
from repro.core.quantizer import LayerwiseConfig, QuantizerConfig
from repro.data.synthetic import regression_shards
from repro.dist.qgadmm import DistConfig, QGADMMTrainer, init_state
from repro.obs import checks, record, trace
from repro.sim import SimConfig, simulate


# --------------------------------------------------------------- fixtures --
class MixedModel:
    """Mixed-precision pytree (f32 + bf16 + zero-size leaf), same shape as
    the wire-path suite's model so telemetry covers every leaf kind."""

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


def _assert_states_equal(sa, sb, msg=""):
    for field in sa._fields:
        la = jax.tree.leaves(getattr(sa, field))
        lb = jax.tree.leaves(getattr(sb, field))
        assert len(la) == len(lb)
        for a, b in zip(la, lb):
            np.testing.assert_array_equal(
                np.asarray(a).view(np.uint8) if a.dtype == jnp.bfloat16
                else np.asarray(a),
                np.asarray(b).view(np.uint8) if b.dtype == jnp.bfloat16
                else np.asarray(b),
                err_msg=f"state field {field} diverged {msg}")


# ----------------------------------------------------- record: schema -------
def test_validate_record_round_trip_every_kind(tmp_path):
    """Every record constructor emits a record that validates and survives
    the JSONL round trip byte-for-byte."""
    recs = [
        record.manifest_record({"rho": 0.5}, seed=3, topology="ring",
                               num_workers=8, extra={"cli": "test"}),
        record.step_record(0, {"loss": np.float32(1.5),
                               "leaf_bits": np.arange(3.0)}, wall_s=0.1),
        record.round_record(2, t_s=1.25, loss=0.7,
                            metrics={"energy_j": 3.0}),
        record.summary_record({"steps": 10, "s_per_step": 0.1}),
        record.bench_record("wire", [{"impl": "jnp", "num_workers": 4}]),
    ]
    path = tmp_path / "run.jsonl"
    with open(path, "w") as f:
        for rec in recs:
            record.validate_record(rec)
            f.write(json.dumps(rec) + "\n")
    loaded = record.validate_run(str(path))
    assert [r["kind"] for r in loaded] == list(record.RECORD_KINDS)
    # numpy values were jsonified at construction time
    assert loaded[1]["metrics"]["loss"] == 1.5
    assert loaded[1]["metrics"]["leaf_bits"] == [0.0, 1.0, 2.0]
    assert loaded[0]["config_hash"] == record.config_hash({"rho": 0.5})


def test_validate_record_rejects_malformed():
    with pytest.raises(ValueError, match="schema"):
        record.validate_record({"kind": "step"})
    with pytest.raises(ValueError, match="kind"):
        record.validate_record({"schema": record.SCHEMA, "kind": "nope"})
    with pytest.raises(ValueError, match="metrics"):
        record.validate_record({"schema": record.SCHEMA, "kind": "step",
                                "step": 0, "metrics": {}})
    with pytest.raises(ValueError, match="topology"):
        record.validate_record({"schema": record.SCHEMA, "kind": "manifest",
                                "config": {}, "topology": None})


def test_validate_run_requires_manifest_first(tmp_path):
    path = tmp_path / "bad.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps(record.step_record(0, {"loss": 1.0})) + "\n")
    with pytest.raises(ValueError, match="manifest"):
        record.validate_run(str(path))


def test_config_hash_stable_and_order_insensitive():
    a = record.config_hash({"b": 2, "a": 1})
    b = record.config_hash({"a": 1, "b": 2})
    assert a == b and len(a) == 12
    assert record.config_hash({"a": 1, "b": 3}) != a


def test_metrics_log_windows_and_file(tmp_path):
    path = tmp_path / "log.jsonl"
    manifest = record.manifest_record({}, seed=0, topology="chain",
                                      num_workers=2)
    with record.MetricsLog(str(path), manifest, log_every=2) as mlog:
        for step in range(5):
            mlog.append(step, {"loss": jnp.float32(step)})
            drained = mlog.maybe_drain(step)
            assert bool(drained) == (step % 2 == 1)
        mlog.close(summary={"steps": 5})
    recs = record.validate_run(str(path))
    kinds = [r["kind"] for r in recs]
    assert kinds == ["manifest"] + ["step"] * 5 + ["summary"]
    assert [r["step"] for r in recs[1:6]] == list(range(5))
    assert all(r["wall_s"] > 0 for r in recs[1:6])


# ------------------------------------------------- trainer: telemetry -------
@pytest.mark.parametrize("variant", ["plain", "censored", "layerwise"])
def test_telemetry_parity_bitwise(variant):
    """Telemetry on == telemetry off, bitwise, for every state field —
    with the on-run's metrics buffered through a draining MetricsLog
    exactly as launch.train wires it."""
    kw = {}
    if variant == "censored":
        kw["censor"] = CensorConfig(tau=0.5, xi=0.95)
    if variant == "layerwise":
        kw["layerwise"] = LayerwiseConfig(bits=(4, 2, 3, 1),
                                          periods=(1, 2, 1, 1), taus=1e-6)
    tr_on, st_on, batch = _setup(telemetry=True, **kw)
    tr_off, st_off, _ = _setup(telemetry=False, **kw)
    step_on = jax.jit(tr_on.make_train_step())
    step_off = jax.jit(tr_off.make_train_step())
    mlog = record.MetricsLog(log_every=2)   # in-memory, drains mid-run
    for k in range(4):
        st_on, m_on = step_on(st_on, batch)
        mlog.append(k, m_on)
        mlog.maybe_drain(k)
        st_off, m_off = step_off(st_off, batch)
        assert "wire_bits_payload" in m_on
        assert "wire_bits_payload" not in m_off
    mlog.close()
    _assert_states_equal(st_on, st_off, f"(telemetry, {variant})")
    steps = [r for r in mlog.records if r["kind"] == "step"]
    assert len(steps) == 4


@pytest.mark.parametrize("variant", ["plain", "censored", "layerwise"])
def test_telemetry_components_sum_and_checks(variant):
    """The split wire accounting reconciles with the billed total, and the
    live invariants accept a healthy run (check_step_window +
    check_edge_mirrors)."""
    kw = {}
    if variant == "censored":
        kw["censor"] = CensorConfig(tau=0.5, xi=0.95)
    if variant == "layerwise":
        kw["layerwise"] = LayerwiseConfig(bits=(4, 2, 3, 1),
                                          periods=(1, 2, 1, 1), taus=1e-6)
    tr, state, batch = _setup(telemetry=True, **kw)
    step = jax.jit(tr.make_train_step())
    mlog = record.MetricsLog(log_every=10)
    for k in range(3):
        state, metrics = step(state, batch)
        mlog.append(k, metrics)
    recs = mlog.drain()
    checks.check_step_window(tr, state, recs)
    checks.check_edge_mirrors(tr, state)
    for rec in recs:
        m = rec["metrics"]
        assert np.isclose(m["wire_bits_payload"] + m["wire_bits_header"]
                          + m["wire_bits_flags"], m["wire_bits_per_round"],
                          rtol=1e-6)
        if variant == "plain":
            assert m["wire_bits_flags"] == 0.0
            assert m["skip_links"] == 0.0
        if variant == "censored":
            assert m["tx_links"] + m["skip_links"] > 0
        if variant == "layerwise":
            assert len(m["leaf_bits"]) == 4   # one entry per pytree leaf
    assert recs[-1]["metrics"]["participants"] == 4.0


def test_check_step_window_catches_corruption():
    tr, state, batch = _setup(telemetry=True)
    step = jax.jit(tr.make_train_step())
    state, metrics = step(state, batch)
    mlog = record.MetricsLog(log_every=10)
    mlog.append(0, metrics)
    recs = mlog.drain()
    recs[0]["metrics"]["wire_bits_payload"] += 64.0
    with pytest.raises(checks.ObsCheckError):
        checks.check_step_window(tr, state, recs)


def test_check_edge_mirrors_catches_desync():
    tr, state, batch = _setup(telemetry=True)
    step = jax.jit(tr.make_train_step())
    state, _ = step(state, batch)
    lam = jax.tree.map(lambda x: np.array(jax.device_get(x)),
                       state.lam_nbr)
    leaf = jax.tree.leaves(lam)[0]
    # break directed row 0's mirror, held in its owner slot (dst, color)
    leaf[tr.eidx.dst[0] * tr.topo.num_ports + tr.eidx.color[0]] += 10.0
    bad = state._replace(lam_nbr=jax.tree.map(jnp.asarray, lam))
    with pytest.raises(checks.ObsCheckError, match="mirror"):
        checks.check_edge_mirrors(tr, bad)


def test_wire_bits_components_match_total_exactly():
    """Static (non-censored) accounting is exact, not just close: the
    component split recomputes the same integers as wire_bits_per_round."""
    tr, state, batch = _setup(telemetry=True)
    total = float(tr.wire_bits_per_round(state.theta))
    pay, hdr, flg = (float(x) for x in tr.wire_bits_components(state.theta))
    assert pay + hdr + flg == total
    assert flg == 0.0


# ------------------------------------------------------- sim: traces --------
@pytest.fixture(scope="module")
def sim_problem():
    xs, ys, _ = regression_shards(n_workers=6, samples=240, d=3, seed=1)
    return jnp.asarray(xs), jnp.asarray(ys)


@pytest.mark.parametrize("engine", ["events", "vectorized"])
def test_trace_export_valid_and_bits_reconcile(sim_problem, tmp_path,
                                               engine):
    """Perfetto export from both engines: the file loads, per-track X
    timestamps are monotone, and the summed tx bits equal
    Timeline.total_bits() — plus the live timeline/trace invariants."""
    xs, ys = sim_problem
    cfg = GADMMConfig(rho=24.0, quantize=True, qcfg=QuantizerConfig(bits=2))
    res = simulate(xs, ys, cfg,
                   SimConfig(topology="ring", rounds=5, seed=0,
                             engine=engine),
                   censor=CensorConfig(tau=1.0, xi=0.9))
    events = trace.timeline_trace(res.timeline)
    path = tmp_path / f"{engine}.trace.json"
    trace.write_trace(str(path), events)
    evs = trace.load_trace(str(path))   # validates on load
    # per-(pid, tid) monotone timestamps for duration events
    last = {}
    for ev in evs:
        if ev.get("ph") != "X":
            continue
        key = (ev["pid"], ev["tid"])
        assert ev["ts"] >= last.get(key, -1.0), key
        last[key] = ev["ts"]
    assert trace.trace_tx_bits(evs) == res.timeline.total_bits()
    checks.check_timeline(res.timeline)
    checks.check_trace(evs, res.timeline)


def test_trace_truncation_warns_and_stays_valid(sim_problem, capsys):
    xs, ys = sim_problem
    cfg = GADMMConfig(rho=24.0, quantize=False)
    res = simulate(xs, ys, cfg, SimConfig(topology="ring", rounds=4, seed=0))
    events = trace.timeline_trace(res.timeline, max_events=20)
    assert "truncated" in capsys.readouterr().out.lower()
    trace.validate_trace({"traceEvents": events})
    # truncated export bills fewer bits; check_trace skips the reconcile
    assert trace.trace_tx_bits(events) < res.timeline.total_bits()
    checks.check_trace(events, res.timeline)


def test_timeline_dedupe_array_and_list_agree(sim_problem):
    """Timeline and ArrayTimeline answer the shared TimelineBase queries
    identically for the same run (vectorized parity corollary)."""
    xs, ys = sim_problem
    cfg = GADMMConfig(rho=24.0, quantize=True, qcfg=QuantizerConfig(bits=2))
    scfg = dict(topology="ring", rounds=5, seed=0)
    ev = simulate(xs, ys, cfg, SimConfig(engine="events", **scfg))
    vec = simulate(xs, ys, cfg, SimConfig(engine="vectorized", **scfg))
    assert ev.timeline.total_bits() == vec.timeline.total_bits()
    assert np.isclose(ev.timeline.total_energy_j(),
                      vec.timeline.total_energy_j(), rtol=1e-9)
    np.testing.assert_allclose(ev.timeline.per_worker_energy_j(),
                               vec.timeline.per_worker_energy_j(),
                               rtol=1e-9)
    assert ev.timeline.rounds_completed() == vec.timeline.rounds_completed()
    # tx records still reachable as a list on the event engine (legacy API)
    assert sum(t.bits for t in ev.timeline.tx) == ev.timeline.total_bits()
    f = vec.timeline.tx_fields()
    assert set(f) == {"t", "src", "dst", "bits", "energy_j", "airtime_s",
                      "attempt", "rnd"}


# -------------------------------------------------- committed artifacts -----
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_committed_bench_wire_schema():
    with open(os.path.join(ROOT, "BENCH_wire.json")) as f:
        doc = json.load(f)
    record.validate_bench_wire(doc)
    record.validate_record(record.bench_record("wire", doc))


def test_committed_bench_sim_schema():
    with open(os.path.join(ROOT, "BENCH_sim.json")) as f:
        doc = json.load(f)
    record.validate_bench_sim(doc)
    record.validate_record(record.bench_record("sim", doc))


def test_write_bench_rejects_malformed(tmp_path):
    with pytest.raises(ValueError, match="non-empty list"):
        record.write_bench(str(tmp_path / "w.json"), [], "wire")
    with pytest.raises(ValueError, match="sections"):
        record.write_bench(str(tmp_path / "s.json"), {"scenarios": []},
                           "sim")
    assert not (tmp_path / "w.json").exists()


# ------------------------------------------------------- report CLI ---------
def _write_run(path, loss0):
    manifest = record.manifest_record({"rho": 0.5}, seed=0, topology="ring",
                                      num_workers=4)
    with record.MetricsLog(str(path), manifest, log_every=2) as mlog:
        for k in range(6):
            mlog.append(k, {"loss": loss0 / (k + 1),
                            "wire_bits_per_round": 1024.0,
                            "skip_rate": 0.25})
            mlog.maybe_drain(k)
        mlog.close(summary={"steps": 6, "s_per_step": 0.01})


def test_report_cli_single_and_diff(tmp_path, capsys):
    from repro.launch import report
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_run(a, 2.0)
    _write_run(b, 1.0)
    report.main([str(a), "--target", "0.5"])
    out = capsys.readouterr().out
    assert "loss_last" in out and "wire_bits" in out
    report.main([str(a), str(b)])
    out = capsys.readouterr().out
    assert "B/A" in out
