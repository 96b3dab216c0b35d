"""Chrome trace-event (Perfetto-loadable) export.

Two producers share the format:

  * ``timeline_trace`` converts a sim ``Timeline``/``ArrayTimeline`` into
    one track per worker (pid 0): every transmission is an ``X`` span at
    its exact simulated start/airtime, annotated with bits, destination,
    round and censor/retransmit provenance; unicast sends additionally
    emit ``s``/``f`` flow arrows from the source span to the arrival on
    the destination track; drops/joins and retransmissions are instants;
    global round completions land on a "rounds" track.

  * ``TraceWriter`` records host wall-clock spans (pid 1) around trainer
    dispatch/drain/compile phases — each span also enters a
    ``jax.profiler.TraceAnnotation`` so the same names show up inside an
    XLA profile when one is being captured.

Load either output at https://ui.perfetto.dev or chrome://tracing.
"""
from __future__ import annotations

import contextlib
import json
import time

import numpy as np

_US = 1e6   # trace timestamps are microseconds


# ------------------------------------------------------------ TraceWriter ---
class TraceWriter:
    """Wall-clock span recorder for host-side phases."""

    def __init__(self) -> None:
        self.events: list[dict] = []
        self._t0 = time.perf_counter()
        self.events.append({"ph": "M", "pid": 1, "tid": 0,
                            "name": "process_name",
                            "args": {"name": "host"}})

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * _US

    @contextlib.contextmanager
    def span(self, name: str, tid: int = 0, **args):
        ts = self._now_us()
        ann = None
        try:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        except Exception:           # profiler unavailable: spans still count
            ann = None
        try:
            yield
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)
            self.events.append({"name": name, "ph": "X", "pid": 1,
                                "tid": tid, "ts": ts,
                                "dur": self._now_us() - ts,
                                "args": args or {}})

    def write(self, path: str) -> None:
        write_trace(path, self.events)


# ------------------------------------------------------- timeline -> trace --
def timeline_trace(timeline, max_events: int = 500_000) -> list[dict]:
    """Trace events for a sim run.  Consumes the shared
    ``TimelineBase.tx_fields()`` accessor, so the events engine and the
    vectorized engine export identically."""
    f = timeline.tx_fields()
    t, src, dst = f["t"], f["src"], f["dst"]
    bits, energy = f["bits"], f["energy_j"]
    air, attempt, rnd = f["airtime_s"], f["attempt"], f["rnd"]
    n_tx = len(t)
    events: list[dict] = [
        {"ph": "M", "pid": 0, "tid": 0, "name": "process_name",
         "args": {"name": "sim"}}]
    for w in range(timeline.n):
        events.append({"ph": "M", "pid": 0, "tid": int(w),
                       "name": "thread_name",
                       "args": {"name": f"worker {w}"}})
    events.append({"ph": "M", "pid": 0, "tid": timeline.n,
                   "name": "thread_name", "args": {"name": "rounds"}})

    limit = max_events
    if n_tx > limit:
        print(f"repro.obs: trace truncated to first {limit} of {n_tx} "
              f"transmissions")
    for i in range(min(n_tx, limit)):
        dur = max(float(air[i]), 1e-9) * _US
        ts = float(t[i]) * _US
        name = (f"retx r{int(rnd[i])}" if attempt[i] > 0
                else f"tx r{int(rnd[i])}")
        events.append({
            "name": name, "ph": "X", "pid": 0, "tid": int(src[i]),
            "ts": ts, "dur": dur,
            "args": {"bits": float(bits[i]), "dst": int(dst[i]),
                     "round": int(rnd[i]), "attempt": int(attempt[i]),
                     "energy_j": float(energy[i])}})
        if attempt[i] > 0:
            events.append({"name": "retransmit", "ph": "i", "s": "t",
                           "pid": 0, "tid": int(src[i]), "ts": ts,
                           "args": {"attempt": int(attempt[i])}})
        if dst[i] >= 0:   # unicast: flow arrow source span -> arrival
            flow = {"cat": "link", "name": "link", "id": int(i)}
            events.append({**flow, "ph": "s", "pid": 0,
                           "tid": int(src[i]), "ts": ts})
            events.append({**flow, "ph": "f", "bp": "e", "pid": 0,
                           "tid": int(dst[i]), "ts": ts + dur})
    for w, td in getattr(timeline, "dropped_at", {}).items():
        events.append({"name": "drop", "ph": "i", "s": "p", "pid": 0,
                       "tid": int(w), "ts": float(td) * _US, "args": {}})
    for k, tk in enumerate(timeline.global_round_times()):
        events.append({"name": f"round {k}", "ph": "i", "s": "t",
                       "pid": 0, "tid": timeline.n,
                       "ts": float(tk) * _US, "args": {"round": k}})
    return events


# --------------------------------------------------------------- file I/O ---
def write_trace(path: str, events: list[dict]) -> None:
    with open(path, "w") as fh:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, fh)


def load_trace(path: str) -> list[dict]:
    with open(path) as fh:
        doc = json.load(fh)
    return validate_trace(doc)


def validate_trace(doc) -> list[dict]:
    """The Perfetto-loadability contract the tests and REPRO_CHECK assert:
    JSON object format, every event carries ph/pid/tid (+ ts except
    metadata), X spans have non-negative dur, and per-track timestamps of
    complete events are monotone non-decreasing (both engines emit in
    time order)."""
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("trace must be a {'traceEvents': [...]} object")
    events = doc["traceEvents"]
    last: dict[tuple, float] = {}
    for ev in events:
        if not isinstance(ev, dict) or "ph" not in ev:
            raise ValueError(f"bad trace event: {ev!r}")
        if "pid" not in ev or "tid" not in ev:
            raise ValueError(f"trace event missing pid/tid: {ev!r}")
        if ev["ph"] == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            raise ValueError(f"trace event missing ts: {ev!r}")
        if ev["ph"] == "X":
            if ev.get("dur", -1) < 0:
                raise ValueError(f"X event needs dur >= 0: {ev!r}")
            key = (ev["pid"], ev["tid"])
            if ts < last.get(key, float("-inf")):
                raise ValueError(
                    f"non-monotone ts on track {key}: {ts} after "
                    f"{last[key]}")
            last[key] = ts
    return events


def trace_tx_bits(events: list[dict]) -> float:
    """Sum of billed bits over tx spans — cross-checked against
    ``Timeline.total_bits()`` by the tests and REPRO_CHECK."""
    return float(np.sum([ev["args"]["bits"] for ev in events
                         if ev.get("ph") == "X" and ev.get("pid") == 0
                         and "bits" in ev.get("args", {})]))
