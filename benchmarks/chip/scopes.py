#!/usr/bin/env python3
"""Split a traced window's device time by the layers of the round.

The program wraps each layer of its round in a named scope
"qgadmm.<layer>" (`repro.dist.qgadmm.LAYERS`), and the compiled module
keeps it in each instruction's metadata (`op_name`), also where a
transform wraps it (`vmap(qgadmm.local_solve)`, `transpose(jvp(...))`).
The trace's op events carry only the instruction's text, so each event is
joined to its layer by instruction name through the compiled module's
text (`Compiled.as_text()`).  An instruction's layer is:

  * the one "qgadmm.<layer>" token of its own op_name;
  * for a fusion with no metadata of its own (XLA makes such wrappers):
    the one token its fused computation's instructions carry;
  * for any other instruction with no metadata at all (a fusion too, whose
    fused computation carries no token): the one layer its operands have,
    in program order, so a chain of XLA's dynamic-update-slices that
    concatenates the flat wire takes the layer of the rows it writes;
    failing that, the one layer of the ops that read its result (the
    buffer a broadcast zeroes, a loop XLA makes to update a wide slab),
    last first; failing that, the layer of the control op (while,
    conditional, call) that runs its computation.

Anything else is unscoped (its metadata names no layer, or several).

Per device, within the window trace_reduce reads (first to last "bench."
host annotation): a layer's time is the union of its non-control "XLA Ops"
intervals (control ops, trace_reduce.CONTROL, span their bodies' ops,
which have events of their own); the unscoped time is the busy time no
layer's op covers (unscoped ops and the control ops' own time).  Layers
never run at once on a device, so the layers and the unscoped time add up
to the busy time.  Seconds are the mean over the cell's chips.

Run as a script, it measures one cell on the chip: an untraced window and
a traced window of the same length, with the per-layer split of the
traced one:

  python3 benchmarks/chip/scopes.py --workload <cell> --seed <n> \
      [--seconds 3] [--out <dir, keeps the trace and the compiled text>]
"""
from __future__ import annotations

import collections
import pathlib
import re
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chip import trace_reduce  # noqa: E402

TOKEN = re.compile(r"\bqgadmm\.([a-z_]+)")
COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{\s*$")
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>\S+) = .*? (?P<op>[a-z][a-z0-9-]*)\("
    r"(?P<operands>[^)]*)\)")
METADATA = re.compile(r", metadata=\{([^}]*)\}")
CALLS = re.compile(r"\bcalls=%([^\s,]+)")
CALLED = re.compile(r"\b(?:body|condition|to_apply)=%([^\s,}]+)|"
                    r"branch_computations=\{([^}]*)\}")
OPERAND = re.compile(r"%([^\s,()]+)")


def _computations(hlo_text: str) -> dict[str, list[str]]:
    """Computation name -> its instruction lines, in the module's order
    (a computation comes after those it calls)."""
    out, cur = {}, None
    for line in hlo_text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            cur = out.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and INSTRUCTION.match(line):
            cur.append(line)
    return out


def layer_map(hlo_text: str) -> dict[str, str]:
    """Instruction name -> layer, for every instruction of the module that
    has one (the rules of the module docstring)."""
    comps = _computations(hlo_text)
    layers: dict[str, str] = {}
    caller_layer: dict[str, str] = {}
    for comp in reversed(list(comps)):          # callers first
        parsed = [INSTRUCTION.match(line) for line in comps[comp]]
        users = collections.defaultdict(set)
        bare = []               # no metadata, no token in a fused body
        for m, line in zip(parsed, comps[comp]):
            name = m.group("name")
            operands = OPERAND.findall(m.group("operands"))
            for o in operands:
                users[o].add(name)
            meta = METADATA.search(line)
            found = set(TOKEN.findall(meta.group(1))) if meta else set()
            if not meta and m.group("op") == "fusion" and CALLS.search(line):
                body = comps.get(CALLS.search(line).group(1), [])
                found = set(TOKEN.findall("\n".join(body)))
            if not meta and not found:
                found = {layers[o] for o in operands if o in layers}
                bare.append(name)
            if len(found) == 1:
                layers[name] = found.pop()
        for name in reversed(bare):
            if name not in layers:
                found = ({layers[u] for u in users[name] if u in layers}
                         or {caller_layer.get(comp)} - {None})
                if len(found) == 1:
                    layers[name] = found.pop()
        for m, line in zip(parsed, comps[comp]):
            if (m.group("op") in trace_reduce.CONTROL
                    and m.group("name") in layers):
                for body, branches in CALLED.findall(line):
                    for callee in [body] if body else OPERAND.findall(
                            branches):
                        caller_layer.setdefault(callee,
                                                layers[m.group("name")])
    return layers


def reduce(ev: dict, hlo_text: str | None, n_devices: int) -> dict | None:
    """Seconds per layer and unscoped of trace_reduce.load's events, joined
    to the compiled module's text; None without a compiled text."""
    if hlo_text is None:
        return None
    layers = layer_map(hlo_text)
    host = ev["host"]
    if not host:
        raise ValueError("no bench. annotations in the trace")
    w0 = min(s for s, _, _ in host)
    w1 = max(e for _, e, _ in host)
    dev_ids = sorted(ev["devices"])[:n_devices]
    if not dev_ids:
        raise ValueError("no device op events in the trace")
    per_layer = collections.defaultdict(float)
    busy, unscoped = [], []
    arr = lambda iv: np.asarray(iv, np.float64).reshape(-1, 2)
    for d in dev_ids:
        ops, by_layer = [], collections.defaultdict(list)
        for s, e, text, line in ev["devices"][d]:
            if line != trace_reduce.OPS_LINE or e <= w0 or s >= w1:
                continue
            s, e = max(s, w0), min(e, w1)
            ops.append((s, e))
            name, op, _ = trace_reduce.parse(text)
            if op not in trace_reduce.CONTROL and name in layers:
                by_layer[layers[name]].append((s, e))
        covered = 0.0
        for layer, iv in by_layer.items():
            length = trace_reduce._length(trace_reduce._union(arr(iv)))
            per_layer[layer] += length
            covered += length
        busy.append(trace_reduce._length(trace_reduce._union(arr(ops))))
        unscoped.append(busy[-1] - covered)
    n = len(dev_ids)
    return {"layer_s": {k: v * 1e-9 / n for k, v in sorted(per_layer.items())},
            "unscoped_s": float(np.mean(unscoped)) * 1e-9,
            "busy_s": float(np.mean(busy)) * 1e-9,
            "window_s": (w1 - w0) * 1e-9, "devices": n}


# ------------------------------------------------------------ on the chip --
def measure(cell: dict, seed: int, seconds: float, devices, out=None) -> dict:
    """One cell's round: an untraced window, then a traced one of the same
    length, reduced by trace_reduce and by layer.  With `out`, the trace and
    the compiled text are kept there (gzipped)."""
    import gc
    import gzip
    import shutil
    import tempfile
    import time

    import jax
    from chip import correct, reference, run, system as sysmod, traffic

    cfg, traf = cell["cfg"], cell["traffic"]
    pool = traffic.batch_pool(cfg, traf, seed)
    sysmod.cache_dir()
    system = sysmod.build(cfg, traf, devices, seed,
                          reference.family(cfg).init, pool[0])
    hlo = system.step.as_text()
    # unload the state maker, as run.check_rounds does: a loaded program
    # keeps device memory reserved beside the round's
    jax.clear_caches()
    gc.collect()
    run.window(system, pool, 0, 0.0)                      # warm-up round
    _, rounds, elapsed = run.window(system, pool, correct.CHECK_ROUNDS,
                                    seconds)
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="bench-scopes-"))
    try:
        jax.profiler.start_trace(str(tmp))
        try:
            _, t_rounds, t_elapsed = run.window(
                system, pool, correct.CHECK_ROUNDS, seconds,
                annotate=jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        xplane = trace_reduce.find_xplane(tmp)
        ev = trace_reduce.load(xplane)
        red = trace_reduce.reduce_events(ev, len(devices))
        t0 = time.perf_counter()
        sc = reduce(ev, hlo, len(devices))
        reduce_s = time.perf_counter() - t0
        if out is not None:
            out = pathlib.Path(out)
            out.mkdir(parents=True, exist_ok=True)
            with open(xplane, "rb") as f, gzip.open(
                    out / f"{cell['name']}.xplane.pb.gz", "wb") as g:
                shutil.copyfileobj(f, g)
            with gzip.open(out / f"{cell['name']}.hlo.txt.gz", "wt") as g:
                g.write(hlo)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    layers = layer_map(hlo)
    ms = lambda s: 1e3 * s / t_rounds
    return {
        "workload": cell["name"], "seed": seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "ms_per_round": 1e3 * elapsed / rounds,
        "traced_ms_per_round": 1e3 * t_elapsed / t_rounds,
        "traced_rounds": t_rounds,
        "trace_window_ms_per_round": ms(red["window_s"]),
        "busy_ms_per_round": ms(red["busy_s"]),
        "layer_ms_per_round": {k: ms(v) for k, v in sc["layer_s"].items()},
        "unscoped_ms_per_round": ms(sc["unscoped_s"]),
        "unscoped_share": 100.0 * sc["unscoped_s"] / sc["busy_s"],
        "kernel_ms_per_round": {k: ms(v) for k, v in red["kernel_s"].items()},
        "collective_ms_per_round": ms(red["collective_s"]),
        "scopes_reduce_s": reduce_s,
        "top_ops": [[label, ms(s), layers.get(label.split()[0])]
                    for label, s in red["breakdown"]["device_ops"]],
        "idle_gaps": red["breakdown"]["idle_gaps"],
    }


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from chip import run

    cell = run.load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"scopes.py: the cell needs {cell['chips']} TPU chips; JAX "
              f"found {len(devs)} {devs[0].platform!r}", file=sys.stderr)
        return 2
    print(json.dumps(measure(cell, args.seed, args.seconds,
                             devs[:cell["chips"]], args.out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
