"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics read.

Read with `jax.profiler.ProfileData`.  Device planes are the ones named
`/device:TPU:<n>`.  On each, the line "XLA Ops" holds one event per
executed HLO instruction, named by the instruction's text
("%name = shape opcode(operands), attributes"); a control-flow op
(while, conditional, call) spans the ops of its body, which have events
of their own.  The line "Async XLA Ops" spans each asynchronous op from
its start to its done.  A Mosaic kernel is a custom call with target
"tpu_custom_call", named after the jitted function that wraps its
pallas_call (`quantize`, `pack4`, `unpack4`).  The traced window is the
host's span from the harness's first "bench." annotation to the end of
its last one.  Per device, within the window:

  busy         union of the op intervals;
  kernel time  summed durations of a kernel's custom calls (KERNELS);
  collectives  union of the collective ops' intervals, synchronous ones
               and asynchronous ones from start to done, and the part of
               it during which no other (non-control-flow) op runs on the
               device (exposed).

`breakdown`: the device ops that took most time (mean per device), and
the longest idle gaps on the devices named by what the host was doing then
(the innermost "bench." annotation over the gap's midpoint, or "host"
outside every annotation).
"""
from __future__ import annotations

import pathlib
import re

import numpy as np

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_SPAN = "bench."
HLO = re.compile(r"^%(?P<name>\S+) = (?P<shape>.*?) (?P<op>[a-z][a-z0-9-]*)\(")
CONTROL = {"while", "conditional", "call"}
MOSAIC = 'custom_call_target="tpu_custom_call"'
# Mosaic kernels of the wire path, by instruction name.
KERNELS = {
    "quantize": re.compile(r"^quantize(\.\d+)?$"),
    "pack": re.compile(r"^(un)?pack4(\.\d+)?$"),
}
COLLECTIVE = re.compile(r"^(collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all|collective-broadcast|"
                        r"send|recv)")


def parse(text: str) -> tuple[str, str, str]:
    """(instruction name, opcode, short label) of an op event's name."""
    m = HLO.match(text)
    if not m:
        return text[:80], "", text[:80]
    shape = m.group("shape")
    return m.group("name"), m.group("op"), (
        f"{m.group('name')} {m.group('op')} {shape[:60]}")


def find_xplane(trace_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals: np.ndarray) -> np.ndarray:
    """Sorted (n, 2) [start, end) intervals -> disjoint union."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def _length(intervals: np.ndarray) -> float:
    return float(np.sum(intervals[:, 1] - intervals[:, 0])) if len(
        intervals) else 0.0


def _overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two disjoint sorted interval sets."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i, 0], b[j, 0])
        hi = min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def load(path) -> dict:
    """Host annotations and per-device op events of one trace, in ns:
    devices[n] = [(start, end, event name, line name)]."""
    import gzip

    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.suffix == ".gz":
        pd = ProfileData.from_serialized_xspace(gzip.decompress(
            path.read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    host, devices = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, ASYNC_LINE):
                devices.setdefault(int(m.group(1)), []).extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name,
                     line.name) for e in line.events)
            elif not m:
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events if e.name.startswith(HOST_SPAN)]
    return {"host": host, "devices": devices}


def reduce_events(ev: dict, n_devices: int) -> dict:
    host = sorted(ev["host"])
    if not host:
        raise ValueError("no bench. annotations in the trace")
    w0 = host[0][0]
    w1 = max(e for _, e, _ in host)
    window_s = (w1 - w0) * 1e-9
    dev_ids = sorted(ev["devices"])[:n_devices]
    if not dev_ids:
        raise ValueError("no device op events in the trace")
    busy, kernels, coll, exposed = [], {k: 0.0 for k in KERNELS}, [], []
    op_time: dict[str, float] = {}
    gaps = []
    for d in dev_ids:
        ops, coll_iv, other_iv = [], [], []
        for s, e, text, line in ev["devices"][d]:
            if e <= w0 or s >= w1:
                continue
            s, e = max(s, w0), min(e, w1)
            name, op, label = parse(text)
            if COLLECTIVE.match(op):
                coll_iv.append((s, e))
            if line != OPS_LINE:
                continue
            ops.append((s, e))
            if op in CONTROL:
                continue
            if not COLLECTIVE.match(op):
                other_iv.append((s, e))
            op_time[label] = op_time.get(label, 0.0) + (e - s)
            if MOSAIC in text:
                for k, pat in KERNELS.items():
                    if pat.match(name):
                        kernels[k] += e - s
        arr = lambda iv: np.asarray(iv, np.float64).reshape(-1, 2)
        u = _union(arr(ops))
        busy.append(_length(u))
        c_u = _union(arr(coll_iv))
        coll.append(_length(c_u))
        exposed.append(_length(c_u) - _overlap(c_u, _union(arr(other_iv))))
        edges = np.concatenate([[w0], u.reshape(-1), [w1]]).reshape(-1, 2)
        for s, e in edges:
            if e > s:
                gaps.append((e - s, s, e))
    n = len(dev_ids)
    gaps.sort(reverse=True)
    named_gaps = []
    for length, s, e in gaps[:10]:
        mid = 0.5 * (s + e)
        over = [(hs, he, hn) for hs, he, hn in host if hs <= mid <= he]
        name = min(over, key=lambda x: x[1] - x[0])[2] if over else "host"
        named_gaps.append([name, length * 1e-9])
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": float(np.mean(busy)) * 1e-9,
        "kernel_s": {k: v * 1e-9 / n for k, v in kernels.items()},
        "collective_s": float(np.mean(coll)) * 1e-9,
        "collective_exposed_s": float(np.mean(exposed)) * 1e-9,
        "devices": n,
        "breakdown": {"device_ops": [[k, v * 1e-9 / n] for k, v in top],
                      "idle_gaps": named_gaps},
    }


def reduce(path, n_devices: int) -> dict:
    return reduce_events(load(path), n_devices)
