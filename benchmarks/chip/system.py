"""The system under test: the program's compiled Q-SGADMM round.

Builds, from a configuration file and a traffic file, what the program's
launcher (`repro.launch.train.build`) builds, with the configuration as the
benchmark runs it (the launcher's registry fixes the depth): the model from
`repro.models.registry`, `DistConfig` with the launcher's defaults and the
traffic file's deployment, `QGADMMTrainer` on `launch.train.worker_mesh`,
and the compiled step the launcher picks:

  * fewer devices than workers: all workers co-located on one chip,
    `make_train_step()` under `jax.jit(..., donate_argnums=0)`;
  * one device per worker: `jit_train_step` on a (W, 1, 1) mesh.

Weights come from the benchmark (`reference/<family>.py`'s `init`), made on
the devices in one jitted call from the seed, in float32.
"""
from __future__ import annotations

import dataclasses
import functools
import pathlib
import sys
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

from repro.core.censor import CensorConfig  # noqa: E402
from repro.core.gadmm import GADMMConfig  # noqa: E402
from repro.core.quantizer import LayerwiseConfig, QuantizerConfig  # noqa: E402
from repro.dist.qgadmm import DistConfig, QGADMMTrainer, init_state  # noqa: E402
from repro.launch import train as launch  # noqa: E402
from repro.models import registry  # noqa: E402
from repro.models.config import ArchConfig  # noqa: E402

IN_FLIGHT = 2


def arch_config(cfg: dict) -> ArchConfig:
    """The configuration file's ArchConfig fields, as run; a nested group
    (such as "ssm") becomes the dataclass its field is typed with."""
    hints = typing.get_type_hints(ArchConfig)
    kw = {k: v for k, v in cfg.items() if k in hints}
    for k, v in kw.items():
        if isinstance(v, dict):
            kw[k] = _dataclass_of(hints[k])(**v)
    for k in ("n_heads", "n_kv_heads", "d_ff"):
        kw.setdefault(k, 0)
    return ArchConfig(**kw)


def _dataclass_of(hint):
    return next(a for a in (hint, *typing.get_args(hint))
                if dataclasses.is_dataclass(a))


def dist_config(dist: dict, wire_impl: str) -> DistConfig:
    """DistConfig from the traffic file's "dist": every field it sets, the
    quantizer's under "bits", censoring and layerwise widths as their
    configs' fields (or null)."""
    censor, layerwise = dist["censor"], dist["layerwise"]
    return DistConfig(
        num_workers=dist["num_workers"],
        gadmm=GADMMConfig(rho=dist["rho"], quantize=dist["quantize"],
                          qcfg=QuantizerConfig(bits=dist["bits"]),
                          alpha=dist["alpha"]),
        local_iters=dist["local_iters"], local_lr=dist["local_lr"],
        mode=dist["mode"], topology=dist["topology"],
        staleness=dist["staleness"], participation=dist["participation"],
        radius_mode=dist["radius_mode"], pack_wire=dist["pack_wire"],
        censor=None if censor is None else CensorConfig(**censor),
        layerwise=None if layerwise is None else LayerwiseConfig(**layerwise),
        wire_impl=wire_impl)


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number (more than 32 bits)."""
    s = int(seed) % (1 << 64)
    return jnp.asarray(np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


@dataclasses.dataclass
class Program:
    trainer: QGADMMTrainer
    state: object
    step: object               # compiled (state, batch) -> (state, metrics)
    batch_shardings: object
    devices: list
    make_state: object         # jitted seed key -> placed state
    in_flight: int             # rounds the window may queue behind one

    def fresh_state(self, seed: int):
        return self.make_state(seed_key(seed))

    def put(self, host_batch):
        return jax.device_put(host_batch, self.batch_shardings)

    def views(self, state):
        """The arrays the correctness check reads, with the edge tables."""
        e = self.trainer.eidx
        return {"theta": state.theta, "opt_mu": state.opt_mu,
                "theta_hat": state.theta_hat, "hat_edge": state.hat_edge,
                "lam_edge": state.lam_edge, "src": np.asarray(e.src),
                "dst": np.asarray(e.dst), "sign_dst": np.asarray(e.sign_dst)}


def build(cfg: dict, traffic: dict, devices, seed: int, init_fn,
          host_batch, wire_impl: str = "pallas_compiled") -> Program:
    """Trainer, state made on the devices from the seed, compiled step."""
    dist = traffic["dist"]
    w = dist["num_workers"]
    arch = arch_config(cfg)
    model = registry.get_model(arch)
    dcfg = dist_config(dist, wire_impl)
    mesh = launch.worker_mesh(np.asarray(devices), w)
    trainer = QGADMMTrainer(model, arch, dcfg, mesh)
    sharded = mesh.shape["worker"] == w and w > 1

    make = functools.partial(init_state, functools.partial(init_fn, cfg=cfg),
                             dcfg=dcfg)
    shapes = jax.eval_shape(make, jax.ShapeDtypeStruct((2,), jnp.uint32))
    shard = lambda specs: jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    state_sh = shard(trainer.state_specs(shapes))
    make_state = jax.jit(make, out_shardings=state_sh)
    state = make_state(seed_key(seed))
    batch_sh = shard(trainer.batch_specs(host_batch))
    batch0 = jax.device_put(host_batch, batch_sh)
    if sharded:
        step = trainer.jit_train_step(state, batch0)
    else:
        step = jax.jit(trainer.make_train_step(), donate_argnums=0)
    compiled = step.lower(state, batch0).compile()
    # A round whose outputs reuse its input buffers (donated state) can
    # queue behind another in the state's memory; one that does not holds a
    # second copy of the state for every queued round, so it gets none.
    memory = compiled.memory_analysis()
    donates = memory is not None and memory.alias_size_in_bytes > 0
    return Program(trainer=trainer, state=state, step=compiled,
                   batch_shardings=batch_sh, devices=list(devices),
                   make_state=make_state,
                   in_flight=IN_FLIGHT if donates else 0)


def cache_dir() -> str:
    """The program's persistent compile cache (inside the checkout, or
    $JAX_COMPILATION_CACHE_DIR)."""
    return launch.enable_compile_cache()
