"""End-to-end decentralized training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch whisper-tiny \
      --workers 2 --topology chain --per-worker-batch 4 --seq 448 --steps 10

The mesh is built from the devices present (`jax.devices()`).  With one
device, all W workers share it and run the single-device step
(`QGADMMTrainer.make_train_step`); otherwise each worker gets its own group
of devices, a ('worker', 'fsdp', 'model') = (W, 1, n // W) mesh driven by
the sharded `jit_train_step`.  On a TPU the wire codec is the compiled
Pallas kernel, elsewhere the jnp reference.

Tests and emulated meshes run on the CPU: `JAX_PLATFORMS=cpu`, and
`--smoke --devices N` forces N host CPU devices.  On a TPU host,
`python chip_smoke.py [--four-chips]` at the repo root drives this module.
Compiled programs are cached in `$JAX_COMPILATION_CACHE_DIR` when it is set,
otherwise in `<repo>/.jax_cache`.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import pathlib
import sys
import time
from typing import Any, Callable

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.topology import TOPOLOGY_KINDS

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU emulation: run on N forced host CPU devices")
    ap.add_argument("--per-worker-batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--rho", type=float, default=1.0)
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--no-quantize", action="store_true")
    ap.add_argument("--local-iters", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mode", default="gauss-seidel",
                    choices=["gauss-seidel", "jacobi"])
    ap.add_argument("--topology", default="chain",
                    choices=list(TOPOLOGY_KINDS),
                    help="worker graph (ring: even workers; torus2d: "
                         "workers %% 4 == 0)")
    ap.add_argument("--censor", action="store_true",
                    help="CQ-GGADMM censored transmissions")
    ap.add_argument("--censor-tau", type=float, default=0.05)
    ap.add_argument("--censor-xi", type=float, default=0.9)
    ap.add_argument("--staleness", type=int, default=0,
                    help="S>0 pipelines the exchange: compute runs against "
                         "S-round-old neighbor hats while S payload rounds "
                         "stay in flight (dist.qgadmm staleness pipeline)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="per-round Bernoulli participation rate in (0, 1]; "
                         "<1 drops workers from random rounds with "
                         "degree-renormalized neighbor sums "
                         "(DistConfig.participation)")
    ap.add_argument("--layerwise", action="store_true",
                    help="L-FGADMM per-leaf wire: large leaves transmit "
                         "every --layerwise-period rounds at per-leaf bit "
                         "widths (DistConfig.layerwise)")
    ap.add_argument("--layerwise-period", type=int, default=2,
                    help="exchange period of the large leaves (top "
                         "half of the model by parameter count)")
    ap.add_argument("--bit-budget", type=int, default=None, metavar="BITS",
                    help="adaptive per-leaf bit allocation under a fixed "
                         "sum(bits_l * d_l) payload budget per "
                         "transmission (implies --layerwise)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10,
                    help="drain/print telemetry every N steps (one batched "
                         "device_get per window; no per-step host sync)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write repro.obs/v1 JSONL run records here "
                         "(manifest first line, step records per window)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event file of host-side "
                         "compile/dispatch/drain spans (Perfetto-loadable)")
    return ap.parse_args(argv)


def enable_compile_cache() -> str:
    """Persistent compile cache: JAX reads $JAX_COMPILATION_CACHE_DIR itself
    when it is set; otherwise the cache lives at the fixed <repo>/.jax_cache
    (the path is part of the cache key, so it never moves).

    The key covers the program's metadata too: the round's layer scopes
    (dist.qgadmm.LAYERS) live only there, and a program that differs from a
    cached one in its metadata alone would otherwise load with the cached
    compile's op names, in its HLO text and in every profile of it."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def worker_mesh(devices, num_workers: int) -> Mesh:
    """('worker', 'fsdp', 'model') mesh over the devices present.

    n >= W devices: one worker per group of n // W devices (the remainder
    idles), (W, 1, n // W).  One device: a (1, 1, 1) mesh that all W
    workers share through the single-device step."""
    devices = np.asarray(devices)
    n = devices.size
    per_worker = n // num_workers
    if per_worker == 0 and n > 1:
        raise ValueError(f"{num_workers} workers on {n} devices: use one "
                         f"device, or at least one device per worker")
    if per_worker == 0:
        return Mesh(devices.reshape(1, 1, 1), ("worker", "fsdp", "model"))
    grid = devices[:num_workers * per_worker].reshape(num_workers, 1,
                                                      per_worker)
    return Mesh(grid, ("worker", "fsdp", "model"))


@dataclasses.dataclass
class Run:
    """A built training run: trainer, placed state and the compiled step."""

    trainer: Any
    state: Any
    step_fn: Any            # compiled (state, batch) -> (state, metrics)
    compile_s: float
    next_batch: Callable[[], Any]   # next placed batch
    mlog: Any               # repro.obs MetricsLog (records kept in memory)
    tw: Any                 # repro.obs TraceWriter or None
    start: int = 0


def _span(tw, name, **kw):
    return tw.span(name, **kw) if tw else contextlib.nullcontext()


def build(args: argparse.Namespace, **dist_overrides) -> Run:
    """Mesh, model, trainer, state and the compiled step for `args`.

    `dist_overrides` replace DistConfig fields (e.g. a comparison run on
    another codec); the codec otherwise follows the backend."""
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
        jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()

    from repro.core.censor import CensorConfig
    from repro.core.gadmm import GADMMConfig
    from repro.core.quantizer import LayerwiseConfig, QuantizerConfig
    from repro.data.pipeline import ExtraInputs, LMShardLoader
    from repro.dist.qgadmm import DistConfig, QGADMMTrainer, init_state
    from repro.models import registry
    from repro.obs import record, trace
    from repro.train import checkpoint

    wmesh = worker_mesh(jax.devices(), args.workers)
    print(f"mesh: {dict(wmesh.shape)}")

    cfg = registry.get_config(args.arch, smoke=args.smoke)
    model = registry.get_model(cfg)
    dcfg = DistConfig(
        num_workers=args.workers,
        gadmm=GADMMConfig(rho=args.rho, quantize=not args.no_quantize,
                          qcfg=QuantizerConfig(bits=args.bits), alpha=0.01),
        local_iters=args.local_iters, local_lr=args.lr, mode=args.mode,
        topology=args.topology, staleness=args.staleness,
        participation=args.participation,
        wire_impl=("pallas_compiled" if jax.default_backend() == "tpu"
                   else "jnp"),
        censor=(CensorConfig(tau=args.censor_tau, xi=args.censor_xi)
                if args.censor else None),
        layerwise=(LayerwiseConfig(large_leaf_period=args.layerwise_period,
                                   budget_bits=args.bit_budget)
                   if args.layerwise or args.bit_budget is not None
                   else None))
    dcfg = dataclasses.replace(dcfg, **dist_overrides)
    trainer = QGADMMTrainer(model, cfg, dcfg, wmesh)

    loader = LMShardLoader(args.workers, args.per_worker_batch, args.seq,
                           cfg.vocab)

    def next_batch():
        b = loader.next_batch()
        if cfg.family == "vlm":
            b["patches"] = ExtraInputs.patches(
                args.workers, args.per_worker_batch, cfg.n_patches, cfg.d_model)
        if cfg.family == "audio":
            b["frames"] = ExtraInputs.frames(
                args.workers, args.per_worker_batch, cfg.encoder_frames,
                cfg.d_model)
        return jax.device_put(b, jax.tree.map(
            lambda s: NamedSharding(wmesh, s), trainer.batch_specs(b),
            is_leaf=lambda x: isinstance(x, PartitionSpec)))

    tw = trace.TraceWriter() if args.trace else None
    state = init_state(lambda k: model.init(k, cfg), jax.random.PRNGKey(0),
                       dcfg)
    batch0 = next_batch()
    state, _ = trainer.place(state, batch0)
    sharded = wmesh.shape["worker"] == args.workers
    if sharded:
        step = trainer.jit_train_step(state, batch0)
    else:   # all workers co-located on the one device
        step = jax.jit(trainer.make_train_step(), donate_argnums=0)
    t0 = time.perf_counter()
    with _span(tw, "compile"):
        step_fn = step.lower(state, batch0).compile()
    compile_s = time.perf_counter() - t0

    start = 0
    if args.ckpt_dir and (s := checkpoint.latest_step(args.ckpt_dir)) is not None:
        state = checkpoint.restore(args.ckpt_dir, s, state)
        state, _ = trainer.place(state, batch0)
        start = s
        print(f"restored step {s}")

    manifest = record.manifest_record(
        dcfg, seed=0, topology=args.topology, num_workers=args.workers,
        extra={"cli": "launch.train", "arch": args.arch,
               "steps": args.steps, "mesh": dict(wmesh.shape),
               "local_solves": trainer.local_solves(sharded)})
    mlog = record.MetricsLog(path=args.metrics_out, manifest=manifest,
                             log_every=args.log_every)
    return Run(trainer=trainer, state=state, step_fn=step_fn,
               compile_s=compile_s, next_batch=next_batch, mlog=mlog, tw=tw,
               start=start)


def steps(run: Run, args: argparse.Namespace):
    """The training loop; yields each step index once `run.state` holds
    that step's result.  Metrics drain every --log-every steps, where the
    repro.obs invariants run too when enabled."""
    from repro.obs import checks
    from repro.train import checkpoint

    trainer, dcfg = run.trainer, run.trainer.dcfg
    check = checks.enabled(dcfg)
    t0 = time.time()

    def show(rec):
        m = rec["metrics"]
        extra = (f" skip={m['skip_rate']:.2f} "
                 f"wire_bits={m['wire_bits_per_round']:.3g}"
                 if args.censor or dcfg.layerwise is not None else "")
        print(f"step {rec['step'] + 1}: loss={m['loss']:.4f} "
              f"resid={m['consensus_resid']:.4f} "
              f"R={m['radius_mean']:.5f}"
              f"{extra} "
              f"({rec['wall_s']:.2f}s/step)")

    for step in range(run.start, args.steps):
        batch = run.next_batch()
        with _span(run.tw, "step", step=step):
            run.state, metrics = run.step_fn(run.state, batch)
        # buffer without touching the device arrays; one batched
        # device_get per --log-every window
        run.mlog.append(step, metrics)
        if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
            with _span(run.tw, "drain", step=step):
                recs = run.mlog.drain()
            if recs:
                show(recs[-1])
            if check and recs:
                checks.check_step_window(trainer, run.state, recs)
                checks.check_edge_mirrors(trainer, run.state)
        if args.ckpt_dir and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            checkpoint.save(args.ckpt_dir, step + 1, run.state)
        yield step
    dt = time.time() - t0
    steps_run = max(args.steps - run.start, 1)
    run.mlog.close(summary={"steps": args.steps, "wall_s": dt,
                            "s_per_step": dt / steps_run,
                            "checked": bool(check)})
    if args.metrics_out:
        print(f"wrote {args.metrics_out}")
    if run.tw:
        run.tw.write(args.trace)
        print(f"wrote {args.trace}")
    if check:
        print("REPRO_CHECK: wire accounting + edge mirrors OK")


def main(argv=None):
    args = parse_args(argv)
    run = build(args)
    for _ in steps(run, args):
        pass
    print("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
