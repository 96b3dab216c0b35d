#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <s> --trace <0|1>

A cell of BENCHMARK.json names a configuration (`configs/<config>.json`)
and a traffic file (`workloads/<traffic>.json`); its correctness limits are
`limits/<cell>.json` and each per-layer metric is read by
`metrics/<metric>.py`.  The configuration's "family" names its model
reference (`reference/<family>.py`), the traffic file's "reference" its
round (`reference/<round>.py`).  Everything is found by name.

Set-up (timed as setup_s, from process start to the first timed dispatch):
weights and state made on the devices from the seed, the compiled round of
the program (see system.py) from the persistent compile cache, and the
first CHECK_ROUNDS rounds through the window's own call and feed, with the
correctness readings (correct.py).  The window then dispatches rounds, one
host batch put on the devices per round, at most two rounds in flight, for
--seconds, and ends with a block on the last round.  With --trace 1 a
short window of its own runs under the profiler and the per-layer metrics
are read from its trace.  After the window: peak device memory, then the
program's state is freed and the reference replays the checked rounds.
The device block's `memory_peak_bytes` is the fullest chip's peak buffers
in use plus the memory reserved for the round's temporaries.

The last stdout line is the result JSON; the compared numbers and their
limits are its last key and the last lines on stderr.  Exits non-zero,
printing no result, when JAX finds no TPU or fewer chips than the cell asks.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE.parent))

from chip import reference  # noqa: E402

TRACE_SECONDS = 3.0
MIN_TRACE_ROUNDS = 4


def load_cell(name: str) -> dict:
    """The cell's BENCHMARK.json entry with its configuration, traffic,
    limits and metric lists."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "name": name, "chips": cell["chips"],
        "cfg": json.loads((ROOT / conf["file"]).read_text()),
        "traffic": json.loads(
            (HERE / "workloads" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads((HERE / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


# ---------------------------------------------------------------- rounds --
def check_rounds(system, pool, seed, cfg, init_fn):
    """The first CHECK_ROUNDS rounds through the system's own step and feed,
    from the system's initial state, with the readings correct.py compares.
    The state after them is left in `system.state`, and the caller holds no
    other reference to it: a round that does not donate its input would
    otherwise keep that state alive beside the window's.  Returns the
    readings."""
    import jax
    import numpy as np
    from chip import correct, system as sysmod

    state, system.state = system.state, None
    losses = []
    for k in range(correct.CHECK_ROUNDS):
        state, m = system.step(state, system.put(pool[k % len(pool)]))
        losses.append(m["loss"])
        if system.in_flight == 0:
            m["loss"].block_until_ready()
        if k == 0:
            grad = correct.grad_reading(system.views(state)["opt_mu"])
    init = functools.partial(init_fn, cfg=cfg)
    k_init = jax.random.split(sysmod.seed_key(seed))[0]
    sizes = [int(np.prod(a.shape))
             for a in jax.tree.leaves(jax.eval_shape(init, k_init))]
    idx = correct.sample_positions(sizes, seed)
    late = correct.late_reading(system.views(state), init, k_init, idx)
    late["losses"] = [float(x) for x in jax.device_get(losses)]
    late["grad"] = grad
    system.state = state
    del state
    # A loaded program keeps device memory reserved for its temporaries;
    # unload every one but the round (which the system holds), so that the
    # readings' and the state maker's reservations do not sit beside it.
    jax.clear_caches()
    gc.collect()
    return late


def device_peak_bytes(devices) -> int:
    """Peak device memory of the fullest chip: the allocator's high-water
    mark of buffers in use, plus what the runtime holds reserved for the
    loaded programs' temporaries (read while the round is loaded)."""
    peaks = []
    for d in devices:
        s = d.memory_stats() or {}
        peaks.append(int(s.get("peak_bytes_in_use", 0))
                     + int(s.get("bytes_reserved", 0)))
    return max(peaks)


def reference_readings(cfg, traffic, pool, seed, dtype=None, fault=None):
    """The reference's readings of the checked rounds (dtype float32 at
    'highest' precision unless a control dtype is given)."""
    import jax
    import jax.numpy as jnp

    mod = reference.family(cfg)
    with jax.default_matmul_precision("highest"):
        system = RefSystem(cfg, traffic, seed, dtype or jnp.float32, fault)
        late = check_rounds(system, pool, seed, cfg, mod.init)
        late["radius"] = [float(r)
                          for r in jax.device_get(system.state.radius)]
    return late


class RefSystem:
    """The reference round in the program's place (controls and faults)."""

    in_flight = 0

    def __init__(self, cfg, traffic, seed, dtype, fault=None):
        import jax
        from chip import system as sysmod

        mod = reference.family(cfg)
        self.round = reference.round_module(traffic).Round(
            mod.loss, cfg, traffic["dist"], dtype, fault)
        k_init, k_state = jax.random.split(sysmod.seed_key(seed))
        params = jax.jit(lambda k: mod.init(k, cfg))(k_init)
        self.state = self.round.init_state(params, k_state)

    def put(self, host_batch):
        import jax
        return jax.device_put(host_batch)

    def step(self, state, batch):
        return self.round.step(state, batch)

    def views(self, state):
        return {"theta": state.theta, "opt_mu": state.opt_mu,
                "theta_hat": state.theta_hat, "hat_edge": state.hat_edge,
                "lam_edge": state.lam_edge, "src": self.round.src,
                "dst": self.round.dst, "sign_dst": self.round.sign_dst}


def window(system, pool, start: int, seconds: float, annotate=None):
    """Dispatch rounds for `seconds`, one host batch put per round (issued
    as soon as the previous round is dispatched, so its transfer overlaps
    that round), at most `system.in_flight` rounds queued behind the one
    dispatched; block on the last.  Under `annotate` (a traced window) each
    host step is a span and at least MIN_TRACE_ROUNDS run.  The rounds start
    from `system.state` and leave their last state there.  Returns
    (per-round metrics on the host, rounds, seconds)."""
    import contextlib

    import jax

    span = annotate or (lambda name: contextlib.nullcontext())
    least = MIN_TRACE_ROUNDS if annotate else 1
    state, system.state = system.state, None
    metrics = []
    k = start
    t0 = time.perf_counter()
    with span("bench.put"):
        batch = system.put(pool[k % len(pool)])
    while True:
        with span("bench.dispatch"):
            state, m = system.step(state, batch)
        metrics.append(m)
        k += 1
        done = (time.perf_counter() - t0 >= seconds
                and len(metrics) >= least)
        if not done:
            with span("bench.put"):
                batch = system.put(pool[k % len(pool)])
        if len(metrics) > system.in_flight:
            with span("bench.wait"):
                metrics[-1 - system.in_flight]["loss"].block_until_ready()
        if done:
            break
    with span("bench.block"):
        jax.block_until_ready((state, metrics[-1]))
    elapsed = time.perf_counter() - t0
    system.state = state
    return jax.device_get(metrics), len(metrics), elapsed


# ------------------------------------------------------------------ run --
def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             make_system=None) -> dict:
    """Everything of one run after the look for a chip; returns the result
    dict.  `make_system(cell, devices, seed, pool)` replaces the program
    (tests plant faults through it)."""
    import jax
    from chip import correct, counts, peaks, traffic as gen

    cfg, traffic = cell["cfg"], cell["traffic"]
    mod = reference.family(cfg)
    marks = {"start": T_START, "imported": time.perf_counter()}
    pool = gen.batch_pool(cfg, traffic, seed)
    marks["pool"] = time.perf_counter()
    if make_system is None:
        from chip import system as sysmod
        sysmod.cache_dir()
        system = sysmod.build(cfg, traffic, devices, seed, mod.init, pool[0])
    else:
        system = make_system(cell, devices, seed, pool)
    marks["built"] = time.perf_counter()
    readings = check_rounds(system, pool, seed, cfg, mod.init)
    marks["checked"] = time.perf_counter()

    setup_s = time.perf_counter() - T_START
    out_metrics, breakdown, device_extra = {}, None, {}
    if trace:
        per_round, rounds, elapsed, red, breakdown = traced_window(
            system, pool, min(seconds, TRACE_SECONDS), len(devices))
        device_extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        ctx = {"trace": red, "rounds": rounds, "window_s": elapsed,
               "per_round": per_round, "chips": len(devices), "cfg": cfg,
               "traffic": traffic,
               "peaks": peaks.peaks(devices[0].device_kind),
               "counts": counts}
        for m in cell["per_layer"]:
            reader = importlib.import_module(f"chip.metrics.{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        per_round, rounds, elapsed = window(
            system, pool, correct.CHECK_ROUNDS, seconds)
    failed = sum(1 for m in per_round if not math.isfinite(float(m["loss"])))
    peak = device_peak_bytes(devices)
    if not trace:
        values = {"tokens_per_s":
                  rounds * gen.tokens_per_round(traffic) / elapsed,
                  "setup_s": setup_s}
        out_metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell["end_to_end"]}
    del system
    gc.collect()

    marks["window"] = time.perf_counter()
    ref = reference_readings(cfg, traffic, pool, seed)
    marks["reference"] = time.perf_counter()
    names = list(marks)
    print("timing (s): " + ", ".join(
        f"{b} {marks[b] - marks[a]:.2f}" for a, b in zip(names, names[1:])),
        file=sys.stderr)
    dist = traffic["dist"]
    nums = correct.numbers(readings, ref, dist["quantize"],
                           float((1 << dist["bits"]) - 1))
    ok, shown = correct.verdict(nums, cell["limits"])
    result = {"correct": bool(ok and failed == 0), "attempted": rounds,
              "failed": failed, "metrics": out_metrics,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices), "memory_peak_bytes": peak,
                         **device_extra}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = shown
    return result


def traced_window(system, pool, seconds, n_devices):
    """A short window under the profiler; its trace reduced to layers."""
    import jax
    from chip import correct, trace_reduce

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="bench-trace-"))
    try:
        jax.profiler.start_trace(str(tmp))
        try:
            per_round, rounds, elapsed = window(
                system, pool, correct.CHECK_ROUNDS, seconds,
                annotate=jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        red = trace_reduce.reduce(trace_reduce.find_xplane(tmp), n_devices)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return per_round, rounds, elapsed, red, red["breakdown"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"run.py: needs a TPU; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < cell["chips"]:
        print(f"run.py: the cell asks for {cell['chips']} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devs[:cell["chips"]])
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
