"""The wire-path kernels compile for a TPU v5e (described, not attached).

Each case lowers one main-path kernel with interpret=False (the fused
quantize-dequantize, the trainer's q-only quantize, pack4, unpack4) for one
chip of a described v5e:2x2 topology and compiles it with the TPU compiler:
what
Mosaic refuses (casts, vector ops, block shapes) fails here, with no chip.
Sizes: whisper-tiny's flat wire row (the trainer's codec length, one
worker per chip) and an odd length whose row count is not a multiple of
the 256-row block.  Nothing runs; results are checked by the interpret-mode
contract tests (test_kernels.py) and on the chip (chip_smoke.py).  Each
compiled kernel keeps the instruction name by which the benchmark's trace
reduction (benchmarks/chip/trace_reduce.KERNELS) finds it, in isolation
and inside the sharded packed round, where the kernels also sit in the
codec's and the exchange's layer scopes.  The same compiled round holds
only each chip's own worker's neighbor slots and gathers no wire.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.pack import pack as pack_kernel
from repro.kernels.pack.ref import packed_len
from repro.kernels.quantize import quantize as q_kernel

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from chip import trace_reduce  # noqa: E402

ODD_N = 1_000_001       # 7813 rows of 128: not a multiple of 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def whisper_n():
    """Parameters per worker of whisper-tiny at its published config."""
    from repro.models import registry
    cfg = registry.get_config("whisper-tiny")
    model = registry.get_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), cfg))
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))


def _qdq(radius_per_elem, levels_per_elem, fn=q_kernel.quantize_dequantize):
    def make(n, sds):
        f32 = jnp.float32
        r = sds((n,) if radius_per_elem else (), f32)
        lv = sds((n,) if levels_per_elem else (), f32)
        args = (sds((n,), f32), sds((n,), f32), sds((n,), f32), r, lv)
        return (lambda t, h, u, r, lv: fn(t, h, u, r, lv,
                                          interpret=False)), args
    return make


def _pack4(n, sds):
    return (lambda q: pack_kernel.pack4(q, interpret=False),
            (sds((n,), jnp.uint8),))


def _unpack4(n, sds):
    return (lambda p: pack_kernel.unpack4(p, n, interpret=False),
            (sds((packed_len(n),), jnp.uint8),))


KERNELS = {
    "quantize_scalar_radius": _qdq(False, False),
    "quantize_vec_radius": _qdq(True, False),
    "quantize_vec_radius_levels": _qdq(True, True),
    "quantize_q_only_scalar_radius": _qdq(False, False, q_kernel.quantize),
    "quantize_q_only_vec_radius": _qdq(True, False, q_kernel.quantize),
    "quantize_q_only_vec_radius_levels": _qdq(True, True, q_kernel.quantize),
    "pack4": _pack4,
    "unpack4": _unpack4,
}


# The pattern of benchmarks/chip/trace_reduce.KERNELS that must find each
# kernel's compiled custom call (None: neither may), and the kernel's own
# name (its pallas_call's `name=`).
TRACE_NAME = {"pack4": "pack", "unpack4": "pack"}
TRACE_NAME.update({k: "quantize" for k in KERNELS if "q_only" in k})
KERNEL_NAME = {k: "quantize" if "q_only" in k else "quantize_dequantize"
               for k in KERNELS if k.startswith("quantize")}
KERNEL_NAME.update(pack4="pack4", unpack4="unpack4")


def kernel_calls(hlo: str) -> dict[str, str]:
    """Instruction name -> op_name of each Mosaic custom call."""
    out = {}
    for line in hlo.splitlines():
        m = trace_reduce.HLO.match(line.strip())
        if m and trace_reduce.MOSAIC in line:
            meta = re.search(r'op_name="([^"]*)"', line)
            out[m.group("name")] = meta.group(1) if meta else ""
    return out


def trace_names(names) -> set:
    """The trace_reduce.KERNELS patterns the instruction names match."""
    return {k for n in names for k, pat in trace_reduce.KERNELS.items()
            if pat.match(n)}


@pytest.mark.parametrize("size", ["whisper_tiny", "odd"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(kernel, size, one_chip, whisper_n):
    n = whisper_n if size == "whisper_tiny" else ODD_N
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    fn, args = KERNELS[kernel](n, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    calls = kernel_calls(compiled.as_text())
    want = TRACE_NAME.get(kernel)
    assert trace_names(calls) == ({want} if want else set()), calls
    assert all(op.endswith(f"/{KERNEL_NAME[kernel]}/pallas_call")
               for op in calls.values()), calls

@pytest.fixture(scope="module")
def sharded_round(topo):
    """The sharded W=4 round with the packed 4-bit wire (whisper-tiny at
    smoke widths, one worker per chip of v5e:2x2), compiled for the chip,
    with its trainer and the state and batch it takes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.gadmm import GADMMConfig
    from repro.core.quantizer import QuantizerConfig
    from repro.dist import qgadmm
    from repro.launch import train as launch
    from repro.models import registry

    cfg = registry.get_config("whisper-tiny", smoke=True)
    model = registry.get_model(cfg)
    dcfg = qgadmm.DistConfig(
        num_workers=4, gadmm=GADMMConfig(
            rho=1.0, quantize=True, qcfg=QuantizerConfig(bits=4),
            alpha=0.01),
        local_iters=1, local_lr=1e-3, wire_impl="pallas_compiled")
    mesh = launch.worker_mesh(np.asarray(topo.devices), 4)
    tr = qgadmm.QGADMMTrainer(model, cfg, dcfg, mesh)
    state = jax.eval_shape(lambda k: qgadmm.init_state(
        lambda kk: model.init(kk, cfg), k, dcfg), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 2, 16), jnp.int32),
             "labels": jax.ShapeDtypeStruct((4, 2, 16), jnp.int32),
             "frames": jax.ShapeDtypeStruct(
                 (4, 2, cfg.encoder_frames, cfg.d_model), jnp.float32)}
    place = lambda tree, specs: jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, specs, is_leaf=lambda x: isinstance(x, P))
    state = place(state, tr.state_specs(state))
    batch = place(batch, tr.batch_specs(batch))
    compiled = tr.jit_train_step(state, batch).lower(state, batch).compile()
    return tr, state, batch, compiled


def test_step_kernels_keep_their_trace_names(sharded_round):
    """The sharded W=4 round with the packed 4-bit wire (whisper-tiny at
    smoke widths, one worker per chip of v5e:2x2): its Mosaic kernels are
    the ones trace_reduce.KERNELS finds (`quantize`: the q-only codec,
    `pack`: pack4 and unpack4) and sit in the codec's and the exchange's
    layer scopes."""
    hlo = sharded_round[3].as_text()
    calls = kernel_calls(hlo)
    quantize = {n for n, op in calls.items() if "jit(quantize)/" in op}
    pack = {n for n, op in calls.items()
            if re.search(r"jit\((un)?pack4\)/", op)}
    assert quantize and pack and quantize | pack == set(calls), calls
    assert trace_names(quantize) == {"quantize"}
    assert trace_names(pack) == {"pack"}
    assert all(trace_reduce.KERNELS["quantize"].match(n) for n in quantize)
    assert all(trace_reduce.KERNELS["pack"].match(n) for n in pack)
    assert all("qgadmm.codec/" in calls[n]
               and calls[n].endswith("/quantize/pallas_call")
               for n in quantize)
    assert all("qgadmm.exchange/" in calls[n]
               and re.search(r"/(un)?pack4/pallas_call$", calls[n])
               for n in pack)


ALL_GATHER = re.compile(
    r"=\s*\(?(?P<dtype>\w+)\[(?P<dims>[\d,]*)\][^ ]*\)?\s+all-gather"
    r"(-start)?\(")


def test_sharded_round_holds_only_its_workers_slots(sharded_round):
    """Each chip of the sharded round holds, decodes and dual-updates only
    its own worker's neighbor slots: the compiled round all-gathers
    neither the wire (u8) nor any parameter leaf stacked over the workers
    (a hat or an edge row), and its per-chip argument bytes for the
    neighbor state are 2 * C slots of one worker's model (hat and dual,
    C = 2 colors on the chain), where replicated directed-edge rows were
    2 * 2E = 12."""
    tr, state, batch, compiled = sharded_round
    leaf = min(int(np.prod(l.shape[1:])) for l in jax.tree.leaves(state.theta)
               if l.size)
    gathered = []
    for line in compiled.as_text().splitlines():
        m = ALL_GATHER.search(line)
        if m and (m.group("dtype") == "u8" or int(np.prod(
                [int(x) for x in m.group("dims").split(",") if x]))
                >= tr.dcfg.num_workers * leaf):
            gathered.append(line.strip()[:160])
    assert not gathered, gathered

    def arg_bytes(tree):
        return jax.jit(lambda x: x).lower(tree).compile(
            ).memory_analysis().argument_size_in_bytes

    args = compiled.memory_analysis().argument_size_in_bytes
    assert args == arg_bytes((state, batch))
    rest = arg_bytes((state._replace(hat_nbr=(), lam_nbr=()), batch))
    c = tr.topo.num_ports
    assert c == 2 and tr.eidx.num_directed == 6
    assert args - rest == 2 * c * arg_bytes(state.theta)
