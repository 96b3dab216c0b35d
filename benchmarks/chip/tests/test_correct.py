"""The correctness check at a size a CPU test run can hold.

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

For every cell of BENCHMARK.json, at tiny widths and the cell's own
deployment and limits (`limits/<cell>.json`), `run.run_cell` drives a whole
run past the look for a chip:
  * the program (the trainer's round, its codec kernels in interpret mode)
    comes out correct;
  * the control (the reference at bfloat16 in the program's place) and
    each planted fault (a round that returns its state unchanged, a loss
    over half the batch, an exchange that never arrives) come out not
    correct.
"""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))

BENCH = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BENCH["workloads"]]


def tiny_cell(name: str) -> dict:
    """The cell at its family's smoke sizes (`TINY`, `TINY_BATCH`)."""
    from chip import reference, run

    cell = run.load_cell(name)
    cfg = cell["cfg"]
    fam = reference.family(cfg)
    for k, v in fam.TINY.items():
        if isinstance(v, dict):
            cfg[k].update(v)
        else:
            cfg[k] = v
    cell["traffic"]["batch"].update(fam.TINY_BATCH)
    cell["chips"] = 1
    return cell


def faults(name: str) -> list[str]:
    """The planted faults of the cell's reference round."""
    from chip import reference, run

    return list(reference.round_module(run.load_cell(name)["traffic"]).FAULTS)


def program_system(cell, devices, seed, pool):
    from chip import reference, system

    return system.build(cell["cfg"], cell["traffic"], devices, seed,
                        reference.family(cell["cfg"]).init, pool[0],
                        wire_impl="pallas")


def reference_system(dtype_name, fault):
    def make(cell, devices, seed, pool):
        import jax.numpy as jnp
        from chip import run

        return run.RefSystem(cell["cfg"], cell["traffic"], seed,
                             getattr(jnp, dtype_name), fault)
    return make


def run_tiny(name, make_system, seed=2**31 + 17):
    import jax
    from chip import run

    return run.run_cell(tiny_cell(name), seed, 0.2, False,
                        jax.devices()[:1], make_system=make_system)


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    res = run_tiny(name, program_system)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("name,what", [
    (name, what) for name in CELLS for what in ["control"] + faults(name)])
def test_control_and_faults_are_not_correct(name, what):
    make = (reference_system("bfloat16", None) if what == "control"
            else reference_system("float32", what))
    res = run_tiny(name, make)
    assert not res["correct"], res["checks"]
