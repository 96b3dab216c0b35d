"""The wire-path kernels compile for a TPU v5e (described, not attached).

Each case lowers one main-path kernel with interpret=False (the fused
quantize-dequantize, the trainer's q-only quantize, pack4, unpack4) for one
chip of a described v5e:2x2 topology and compiles it with the TPU compiler:
what
Mosaic refuses (casts, vector ops, block shapes) fails here, with no chip.
Sizes: whisper-tiny's flat wire row (the trainer's codec length, one
worker per chip) and an odd length whose row count is not a multiple of
the 256-row block.  Nothing runs; results are checked by the interpret-mode
contract tests (test_kernels.py) and on the chip (chip_smoke.py).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.pack import pack as pack_kernel
from repro.kernels.pack.ref import packed_len
from repro.kernels.quantize import quantize as q_kernel

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


@pytest.mark.parametrize("size", ["whisper_tiny", "odd"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(kernel, size, one_chip, whisper_n):
    n = whisper_n if size == "whisper_tiny" else ODD_N
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    fn, args = KERNELS[kernel](n, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
