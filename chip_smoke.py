#!/usr/bin/env python3
"""Chip smoke test: the Q-SGADMM trainer's main path on a TPU.

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # one host with four chips

One chip: whisper-tiny at its full published config, W=2 workers on a chain,
both co-located on the chip, 8-bit wire, per-worker batch 4 x 448 decoder
tokens, 10 steps — the run `python -m repro.launch.train --arch whisper-tiny
--workers 2 --topology chain --per-worker-batch 4 --seq 448 --steps 10`
makes, driven through repro.launch.train's own build/steps.  It checks the
losses, the repro.obs invariants, sender==receiver hat sync, the compiled
codec against its jnp reference on the trainer's real wire buffers, and
that the compiled step holds the Pallas kernels.

Four chips (and nothing else): W=4 chain, one worker per chip, 4-bit wire,
so every device nibble-packs its payload around a uint8 collective-permute.
The same sharded step with the unpacked jnp codec runs in lockstep on the
same seed and batches; the states are compared after every step.

Everything runs in this one process.  Exits non-zero, printing no result,
when the backend is not a TPU or any check fails; the last line of a
passing run is {"ok": true, "device": {...}}.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
from repro.core.quantizer import levels_of  # noqa: E402
from repro.dist import qgadmm  # noqa: E402
from repro.kernels.quantize import quantize as q_kernel  # noqa: E402
from repro.kernels.quantize import ref as q_ref  # noqa: E402
from repro.launch import train  # noqa: E402

ONE_CHIP = ["--arch", "whisper-tiny", "--workers", "2", "--topology", "chain",
            "--per-worker-batch", "4", "--seq", "448", "--steps", "10"]
FOUR_CHIPS = ["--arch", "whisper-tiny", "--workers", "4", "--topology",
              "chain", "--per-worker-batch", "4", "--seq", "448", "--steps",
              "3", "--bits", "4"]


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def hat_sync_gap(trainer, state) -> float:
    """max |hat_edge[d] - theta_hat[src(d)]|: each receiver's copy of a
    sender's hat against the hat the sender committed."""
    src = jnp.asarray(trainer.eidx.src)

    @jax.jit
    def gap(hat_edge, theta_hat):
        return jnp.max(jnp.stack([
            jnp.max(jnp.abs(e.astype(jnp.float32)
                            - h[src].astype(jnp.float32)))
            for e, h in zip(jax.tree.leaves(hat_edge),
                            jax.tree.leaves(theta_hat))]))

    return float(gap(state.hat_edge, state.theta_hat))


def codec_gaps(trainer, state) -> dict:
    """The compiled codec against quantize_dequantize_ref on the trainer's
    flat wire rows: (theta, theta_hat) after the last step, and (theta, 0),
    the first round's full-range payload.  Each row uses the trainer's own
    global radius and its levels from the state's traced bit width
    (`levels_of`, as the sender and receivers compute them).  Max abs
    differences over the W rows of:
      q             the trainer's q-only kernel `quantize` vs the reference;
      hat           the fused kernel's new hat vs the reference's;
      hat_vs_decode the fused kernel's new hat vs the receivers' decode of
                    q (`qgadmm._decode`), which the sender commits."""
    f32 = jnp.float32
    theta_f = trainer._pad_wire(
        trainer._flatten_rows(jax.tree.leaves(state.theta), f32))
    hat_f = trainer._pad_wire(
        trainer._flatten_rows(jax.tree.leaves(state.theta_hat), f32))
    u = jax.random.uniform(state.key, theta_f.shape, f32)

    def gap(a, b):
        return jnp.max(jnp.abs(a.astype(f32) - b.astype(f32)))

    @jax.jit
    def row_gaps(theta, hat, uu, bits):
        levels = levels_of(bits)
        r = jnp.max(jnp.abs(theta - hat))
        q = q_kernel.quantize(theta, hat, uu, r, levels, interpret=False)
        _, hk = q_kernel.quantize_dequantize(theta, hat, uu, r, levels,
                                             interpret=False)
        qr, hr = q_ref.quantize_dequantize_ref(theta, hat, uu, r, levels)
        return {"q": gap(q, qr), "hat": gap(hk, hr),
                "hat_vs_decode": gap(hk, qgadmm._decode(q, hat, r, levels))}

    out = {}
    for name, hats in (("theta_vs_theta_hat", hat_f),
                       ("theta_vs_zero", jnp.zeros_like(hat_f))):
        rows = [jax.device_get(row_gaps(theta_f[i], hats[i], u[i],
                                        state.bits[i]))
                for i in range(theta_f.shape[0])]
        out[name] = {k: max(float(r[k]) for r in rows) for k in rows[0]}
    return out


def losses_of(run) -> list[float]:
    return [r["metrics"]["loss"] for r in run.mlog.records
            if r["kind"] == "step"]


def one_chip(dev) -> None:
    args = train.parse_args(ONE_CHIP)
    print(f"config: {' '.join(ONE_CHIP)}")
    run = train.build(args, check_invariants=True)
    dcfg = run.trainer.dcfg
    n_params = sum(l.size // l.shape[0]
                   for l in jax.tree.leaves(run.state.theta))
    print(f"wire_impl: {dcfg.wire_impl}  bits: {dcfg.gadmm.qcfg.bits}  "
          f"params/worker: {n_params}")
    print(f"compile_s: {run.compile_s}")
    require(dcfg.wire_impl == "pallas_compiled",
            f"trainer codec on a TPU is {dcfg.wire_impl!r}")
    n_kernels = run.step_fn.as_text().count("tpu_custom_call")
    print(f"step HLO tpu_custom_call: {n_kernels}")
    require(n_kernels > 0, "no tpu_custom_call in the compiled step")

    for _ in train.steps(run, args):
        pass
    losses = losses_of(run)
    print(f"losses: {losses}")
    require(len(losses) == args.steps, f"{len(losses)} losses recorded")
    require(all(math.isfinite(x) for x in losses), "non-finite loss")
    require(losses[-1] < losses[0],
            f"loss did not fall: step 1 {losses[0]} -> step "
            f"{args.steps} {losses[-1]}")
    print("repro.obs checks (check_step_window, check_edge_mirrors): passed")

    print(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")
    gap = hat_sync_gap(run.trainer, run.state)
    print(f"hat_sync max|hat_edge - theta_hat[src]|: {gap}")
    require(gap == 0.0, f"sender/receiver hats out of sync by {gap}")
    for name, g in codec_gaps(run.trainer, run.state).items():
        print(f"codec compiled-vs-ref ({name}): max|dq| {g['q']} "
              f"max|dhat| {g['hat']}; fused kernel hat vs receivers' "
              f"decode: {g['hat_vs_decode']}")
        require(g["q"] == 0.0 and g["hat"] == 0.0,
                f"compiled codec differs from its reference on {name}")


def state_gap(a, b) -> float:
    """max |a - b| over the leaves of two host-side states (0.0 if equal)."""
    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if x.size and not np.array_equal(x, y):
            dt = np.float32 if x.dtype.kind == "f" else np.int64
            worst = max(worst, float(np.max(np.abs(
                x.astype(dt) - y.astype(dt)))))
    return worst


def four_chips(devs) -> None:
    require(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    args = train.parse_args(FOUR_CHIPS)
    print(f"config: {' '.join(FOUR_CHIPS)}")
    # The runs take turns: each holds ~3.6 GB of state per chip and its step
    # needs ~11 GB more, so both at once do not fit a 16 GiB chip.  Run A's
    # state after every step is kept on the host for the comparison.
    packed = train.build(args, check_invariants=True)
    dk = packed.trainer.dcfg
    print(f"run A: wire_impl={dk.wire_impl} pack_wire={dk.pack_wire} "
          f"compile_s={packed.compile_s}")
    require(dk.wire_impl == "pallas_compiled" and dk.pack_wire,
            "run A must use the compiled kernels and the packed wire")

    hlo = packed.step_fn.as_text()
    u8 = [l for l in hlo.splitlines()
          if "collective-permute" in l and "u8[" in l]
    n_kernels = hlo.count("tpu_custom_call")
    print(f"run A HLO: u8 collective-permute lines {len(u8)}, "
          f"tpu_custom_call {n_kernels}")
    require(u8 and n_kernels > 0, "packed exchange or kernels missing")

    # layout: every state leaf and the compiled batch input span 4 devices,
    # one worker row per device
    for leaf in jax.tree.leaves((packed.state.theta, packed.state.opt_mu)):
        shards = leaf.addressable_shards
        require(len({s.device for s in shards}) == 4
                and all(s.data.shape[0] == 1 for s in shards),
                f"state leaf {leaf.shape} not spread one row per device")
    batch_in = jax.tree.leaves(packed.step_fn.input_shardings[0][1])
    for s in batch_in:
        require(len(s.device_set) == 4, f"batch sharding {s} not on 4 devices")
    print(f"layout: state and batch over {len(devs)} devices, one worker "
          f"row each")

    snaps = []
    for _ in train.steps(packed, args):
        snaps.append(jax.device_get(packed.state))
    losses_a = losses_of(packed)
    gap_a = hat_sync_gap(packed.trainer, packed.state)
    print(f"run A hat_sync max|hat_edge - theta_hat[src]|: {gap_a}")
    for d in devs:
        print(f"run A peak_bytes_in_use {d}: "
              f"{d.memory_stats()['peak_bytes_in_use']}")
    del packed
    gc.collect()

    plain = train.build(args, check_invariants=True, pack_wire=False,
                        wire_impl="jnp")
    dp = plain.trainer.dcfg
    print(f"run B: wire_impl={dp.wire_impl} pack_wire={dp.pack_wire} "
          f"compile_s={plain.compile_s}")
    worst = 0.0
    for step in train.steps(plain, args):
        g = state_gap(snaps[step], jax.device_get(plain.state))
        worst = max(worst, g)
        print(f"step {step + 1}: max|state A - state B| {g}")
    gap_b = hat_sync_gap(plain.trainer, plain.state)
    print(f"run B hat_sync max|hat_edge - theta_hat[src]|: {gap_b}")
    for d in devs:
        print(f"peak_bytes_in_use {d} (both runs): "
              f"{d.memory_stats()['peak_bytes_in_use']}")
    print(f"losses A: {losses_a}")
    print(f"losses B: {losses_of(plain)}")
    print("repro.obs checks (check_step_window, check_edge_mirrors): passed "
          "in both runs")
    print(f"max|state A - state B| over {args.steps} steps: {worst}")
    require(all(math.isfinite(x) for x in losses_a + losses_of(plain)),
            "non-finite loss")
    require(worst == 0.0, f"packed kernel run and unpacked jnp run differ "
                          f"by {worst}")
    require((gap_a, gap_b) == (0.0, 0.0),
            f"sender/receiver hats out of sync: A {gap_a}, B {gap_b}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded exchange phase")
    opts = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    require(dev.platform == "tpu",
            f"needs a TPU backend; JAX found platform {dev.platform!r}")
    print(f"device: {dev.device_kind} x{len(devs)}")
    print(f"compile cache: {train.enable_compile_cache()}")
    if opts.four_chips:
        four_chips(devs)
    else:
        one_chip(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
