"""Capture golden trainer outputs for the cross-refactor bitwise parity tier.

Run from the repo root (PYTHONPATH=src) at a known-good revision:

    PYTHONPATH=src python tests/tools/capture_golden_wire.py

Writes ``tests/golden/wire_state_v1.npz``: the final train state and wire
metrics of the unsharded reference trainer after GOLDEN_STEPS steps on the
canonical MixedModel problem, for every topology x censor x pack combination.
``tests/test_wire_path.py::test_golden_state_bitwise`` replays the same runs
against the current code and asserts bitwise equality — this is what pins
"staleness=0 is bitwise-identical to the pre-refactor trainer" across the
port-dense -> edge-indexed state refactor.

bfloat16 leaves are stored bit-cast to uint16 (npz has no bf16 dtype).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.core.censor import CensorConfig
from repro.core.gadmm import GADMMConfig
from repro.core.quantizer import QuantizerConfig
from repro.dist.qgadmm import DistConfig, QGADMMTrainer, init_state

GOLDEN_STEPS = 3
GOLDEN_W = 4
GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "..", "golden",
                           "wire_state_v1.npz")


class MixedModel:
    """Mirrors tests/test_wire_path.py: f32 + bf16 + (0,) leaves."""

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


def golden_cases():
    for topology in ("chain", "ring", "star", "torus2d"):
        for censored in (False, True):
            for pack in (False, True):
                yield topology, censored, pack


def golden_run(topology, censored, pack):
    """One unsharded reference run; returns (state, metrics)."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("worker", "fsdp", "model"))
    dcfg = DistConfig(
        num_workers=GOLDEN_W, topology=topology,
        censor=CensorConfig(tau=0.5, xi=0.95) if censored else None,
        pack_wire=pack, wire_impl="jnp",
        gadmm=GADMMConfig(rho=0.5, quantize=True,
                          qcfg=QuantizerConfig(bits=4), alpha=0.01),
        local_iters=2, local_lr=1e-2)
    tr = QGADMMTrainer(MixedModel, None, dcfg, mesh)
    state = init_state(lambda k: MixedModel.init(k, None),
                       jax.random.PRNGKey(0), dcfg)
    batch = {"x": jax.random.normal(jax.random.PRNGKey(1), (GOLDEN_W, 8, 6)),
             "y": jax.random.normal(jax.random.PRNGKey(2), (GOLDEN_W, 8))}
    step = jax.jit(tr.make_train_step())
    for _ in range(GOLDEN_STEPS):
        state, metrics = step(state, batch)
    return tr, state, metrics


def state_arrays(tr, state, metrics):
    """Flatten (state, metrics) into a {name: ndarray} dict in the GOLDEN
    comparison layout: neighbor hats/duals are projected to per-(worker,
    port-color) views so the dict is independent of the internal state
    layout (port-dense tuples, then directed-edge slabs, now (W, C) owner
    slots)."""
    out = {}

    def put(name, arr):
        a = np.asarray(arr)
        if arr.dtype == jnp.bfloat16:
            a = np.asarray(arr).view(np.uint16)
            name += "#bf16"
        out[name] = a

    views = tr.port_views(state) if hasattr(tr, "port_views") else {
        "hat_nbr": state.hat_nbr, "lam_nbr": state.lam_nbr}
    # states that held directed-edge slabs project them to the golden
    # port-view names
    alias = {"hat_edge": "hat_nbr", "lam_edge": "lam_nbr"}
    for field in state._fields:
        if field == "edge_slot":      # the topology's row map, not state
            continue
        name = alias.get(field, field)
        val = views.get(name, getattr(state, field))
        for i, leaf in enumerate(jax.tree.leaves(val)):
            put(f"{name}.{i}", leaf)
    for k in ("loss", "skip_rate", "wire_bits_per_round"):
        out[f"metric.{k}"] = np.asarray(metrics[k])
    return out


def main():
    blob = {}
    for topology, censored, pack in golden_cases():
        tag = f"{topology}|c{int(censored)}|p{int(pack)}"
        tr, state, metrics = golden_run(topology, censored, pack)
        for name, arr in state_arrays(tr, state, metrics).items():
            blob[f"{tag}|{name}"] = arr
        print("captured", tag, "loss", float(metrics["loss"]))
    os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
    np.savez_compressed(GOLDEN_PATH, **blob)
    print("wrote", GOLDEN_PATH, len(blob), "arrays")


if __name__ == "__main__":
    main()
