#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

  python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \
      --what program,control,unchanged,half_batch,no_exchange

For each seed, the compared numbers (correct.py) of:
  program      the program's compiled round (one build, a fresh state and
               batch pool per seed) through the checked rounds;
  control      the reference in the program's place at bfloat16, the
               precision below the configuration's float32 at the default
               (one-pass bfloat16) matmul precision;
  <fault>      the float32 reference in the program's place with one of
               the FAULTS of the cell's reference round planted.
each against the float32 reference at 'highest' precision.  One JSON line
per (what, seed).  Not run by the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from chip import correct, reference, run, system as sysmod
    from chip import traffic as gen

    cell = run.load_cell(args.workload)
    cfg, traffic = cell["cfg"], cell["traffic"]
    dist = traffic["dist"]
    levels = float((1 << dist["bits"]) - 1)
    whats = args.what.split(",")
    devs = jax.devices()
    # the reference runs on one chip; the program needs the cell's chips
    need = cell["chips"] if "program" in whats else 1
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"calibrate.py: needs {need} TPU chips", file=sys.stderr)
        return 2
    devices = devs[:cell["chips"]]
    sysmod.cache_dir()
    mod = reference.family(cfg)
    faults = reference.round_module(traffic).FAULTS
    seeds = [int(s) for s in args.seeds.split(",")]
    program = None
    for what in whats:
        for seed in seeds:
            pool = gen.batch_pool(cfg, traffic, seed)
            if what == "program":
                if program is None:
                    program = sysmod.build(cfg, traffic, devices, seed,
                                           mod.init, pool[0])
                else:
                    program.state = program.fresh_state(seed)
                got = run.check_rounds(program, pool, seed, cfg, mod.init)
                program.state = None
            else:
                fault = None if what == "control" else what
                if fault is not None and fault not in faults:
                    raise SystemExit(f"unknown reading {what!r}")
                dtype = jnp.bfloat16 if what == "control" else jnp.float32
                got = run.reference_readings(cfg, traffic, pool, seed,
                                             dtype=dtype, fault=fault)
            gc.collect()
            ref = run.reference_readings(cfg, traffic, pool, seed)
            nums = correct.numbers(got, ref, dist["quantize"], levels)
            print(json.dumps({"cell": cell["name"], "what": what,
                              "seed": seed, "numbers": nums,
                              "losses": got["losses"],
                              "ref_losses": ref["losses"]}), flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
