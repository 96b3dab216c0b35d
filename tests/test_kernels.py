"""Per-kernel allclose sweeps vs the pure-jnp ref.py oracles (interpret mode)."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.pack import ops as pack_ops
from repro.kernels.quantize import ops as q_ops
from repro.kernels.quantize import quantize as q_kernel
from repro.kernels.quantize import ref as q_ref

SHAPES = [(7,), (128,), (1000,), (31, 33), (4, 256, 17), (2048, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]
BITS = [1, 2, 4, 8]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("bits", [2, 8])
def test_quantize_matches_ref(shape, dtype, bits):
    key = jax.random.PRNGKey(zlib.crc32(repr((shape, str(dtype), bits)).encode()) % 2**31)
    k1, k2, k3 = jax.random.split(key, 3)
    theta = jax.random.normal(k1, shape).astype(dtype)
    hat = (0.5 * jax.random.normal(k2, shape)).astype(dtype)
    r = jnp.max(jnp.abs(theta.astype(jnp.float32) - hat.astype(jnp.float32)))
    q_p, hat_p = q_ops.quantize_dequantize(theta, hat, k3, r, bits, impl="pallas")
    q_r, hat_r = q_ops.quantize_dequantize(theta, hat, k3, r, bits, impl="ref")
    np.testing.assert_array_equal(np.asarray(q_p), np.asarray(q_r))
    # hat can differ by ~1 f32 ULP (FMA association inside the fused kernel),
    # which may land on a bf16 rounding boundary -> allow 1 bf16 ULP.
    atol = 2e-5 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(
        np.asarray(hat_p, np.float32), np.asarray(hat_r, np.float32), atol=atol
    )


@pytest.mark.parametrize("bits", BITS)
def test_quantize_error_bound(bits):
    """|theta_hat - theta| <= Delta = 2R/(2^b - 1) elementwise."""
    key = jax.random.PRNGKey(bits)
    theta = jax.random.normal(key, (4096,))
    hat0 = jnp.zeros_like(theta)
    r = jnp.max(jnp.abs(theta))
    _, hat = q_ops.quantize_dequantize(theta, hat0, jax.random.PRNGKey(1), r, bits)
    delta = 2 * r / (2**bits - 1)
    assert float(jnp.max(jnp.abs(hat - theta))) <= float(delta) + 1e-5


def test_quantize_zero_radius_is_identity():
    theta = jnp.ones((257,))
    hat = jnp.ones((257,))
    r = jnp.zeros(())
    q, new_hat = q_ops.quantize_dequantize(theta, hat, jax.random.PRNGKey(0), r, 2)
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_allclose(np.asarray(new_hat), np.asarray(hat))


def test_quantize_levels_in_range():
    theta = jax.random.normal(jax.random.PRNGKey(0), (999,))
    hat = jnp.zeros_like(theta)
    r = jnp.max(jnp.abs(theta))
    for bits in BITS:
        q, _ = q_ops.quantize_dequantize(theta, hat, jax.random.PRNGKey(1), r, bits)
        assert int(jnp.max(q)) <= 2**bits - 1


def test_quantize_sender_receiver_consistency():
    """Receiver reconstruction from (q, R, b) equals sender's new hat exactly."""
    from repro.core import quantizer as Q

    theta = jax.random.normal(jax.random.PRNGKey(5), (1234,))
    hat0 = 0.3 * jax.random.normal(jax.random.PRNGKey(6), (1234,))
    r = jnp.max(jnp.abs(theta - hat0))
    bits = jnp.asarray(4, jnp.int32)
    q, hat_sender = Q.quantize_tensor(
        theta, hat0, jax.random.PRNGKey(7), radius=r, bits=bits
    )
    hat_receiver = Q.dequantize_tensor(q, hat0, radius=r, bits=bits)
    np.testing.assert_allclose(np.asarray(hat_sender), np.asarray(hat_receiver), atol=0)


@pytest.mark.parametrize("n", [1, 2, 255, 256, 257, 999, 65536, 70000])
def test_pack_roundtrip_and_ref(n):
    q = jax.random.randint(jax.random.PRNGKey(n), (n,), 0, 16).astype(jnp.uint8)
    pk = pack_ops.pack4(q)
    pk_ref = pack_ops.pack4(q, impl="ref")
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pk_ref))
    un = pack_ops.unpack4(pk, n)
    np.testing.assert_array_equal(np.asarray(un), np.asarray(q))
    un_ref = pack_ops.unpack4(pk_ref, n, impl="ref")
    np.testing.assert_array_equal(np.asarray(un_ref), np.asarray(q))
    assert pk.size <= n // 2 + 256  # ~2x compression (+ row padding)


@pytest.mark.parametrize("n", [1, 3, 127, 129, 250, 257, 300, 511, 1000,
                               4097, 70001])
def test_packed_len_is_the_wire_length_contract(n):
    """packed_len(n) (exported by kernels/pack) IS the wire length both the
    packer and every unpacker must agree on, including every odd size with
    n % 256 != 0 — regression: the dist trainer used to hardcode the
    128 * ceil(n/256) formula."""
    from repro.kernels.pack.ref import LANES, _pad_rows

    assert pack_ops.packed_len(n) == 128 * (-(-n // 256)) == LANES * _pad_rows(n)
    q = jax.random.randint(jax.random.PRNGKey(n), (n,), 0, 16).astype(jnp.uint8)
    for impl in ("ref", "pallas"):
        pk = pack_ops.pack4(q, impl=impl)
        assert pk.size == pack_ops.packed_len(n), (impl, n)
        un = pack_ops.unpack4(pk[: pack_ops.packed_len(n)], n, impl=impl)
        np.testing.assert_array_equal(np.asarray(un), np.asarray(q))


@pytest.mark.parametrize("dtype", DTYPES)
def test_quantize_vector_radius_matches_ref(dtype):
    """Per-element radius (the trainer's per_tensor segment-scalar expansion)
    agrees between the Pallas tile-radius kernel and the broadcasting ref."""
    key = jax.random.PRNGKey(11)
    k1, k2, k3 = jax.random.split(key, 3)
    n = 700  # odd size: exercises radius padding in the tile path
    theta = jax.random.normal(k1, (n,)).astype(dtype)
    hat = (0.5 * jax.random.normal(k2, (n,))).astype(dtype)
    # two "tensors" of 300 + 400 elements with their own radii; one zero
    diff = jnp.abs(theta.astype(jnp.float32) - hat.astype(jnp.float32))
    r_a = jnp.max(diff[:300])
    radius = jnp.concatenate([jnp.full((300,), r_a),
                              jnp.zeros((400,), jnp.float32)])
    u = jax.random.uniform(k3, (n,), jnp.float32)
    levels = jnp.asarray(15.0)
    q_r, hat_r = q_ref.quantize_dequantize_ref(theta, hat, u, radius, levels)
    q_p, hat_p = q_kernel.quantize_dequantize(theta, hat, u, radius, levels,
                                              interpret=True)
    np.testing.assert_array_equal(np.asarray(q_r), np.asarray(q_p))
    np.testing.assert_array_equal(np.asarray(hat_r, np.float32),
                                  np.asarray(hat_p, np.float32))
    # zero-radius segment: untouched hat, all-zero levels
    np.testing.assert_array_equal(np.asarray(q_p[300:]), 0)
    np.testing.assert_array_equal(np.asarray(hat_p[300:]),
                                  np.asarray(hat[300:]))


@pytest.mark.parametrize("dtype", DTYPES)
def test_quantize_vector_levels_matches_ref(dtype):
    """Per-element levels (the trainer's layerwise per-leaf bit widths) agree
    bitwise between the Pallas tile kernel and the ref — under jit on both
    sides: eager XLA fuses the step arithmetic differently (FMA), so the
    parity contract is jitted-ref == kernel, which is also how the trainer
    runs both impls."""
    key = jax.random.PRNGKey(13)
    k1, k2, k3 = jax.random.split(key, 3)
    n = 700
    theta = jax.random.normal(k1, (n,)).astype(dtype)
    hat = (0.5 * jax.random.normal(k2, (n,))).astype(dtype)
    diff = jnp.abs(theta.astype(jnp.float32) - hat.astype(jnp.float32))
    # three "leaves" of 300 + 300 + 100 elements: own radius AND own bits,
    # the last one masked out (radius 0 = unsent leaf)
    radius = jnp.concatenate([jnp.full((300,), jnp.max(diff[:300])),
                              jnp.full((300,), jnp.max(diff[300:600])),
                              jnp.zeros((100,), jnp.float32)])
    levels = jnp.concatenate([jnp.full((300,), 15.0),
                              jnp.full((300,), 3.0),
                              jnp.ones((100,), jnp.float32)])
    u = jax.random.uniform(k3, (n,), jnp.float32)
    q_r, hat_r = jax.jit(q_ref.quantize_dequantize_ref)(
        theta, hat, u, radius, levels)
    q_p, hat_p = jax.jit(
        lambda *a: q_kernel.quantize_dequantize(*a, interpret=True))(
        theta, hat, u, radius, levels)
    np.testing.assert_array_equal(np.asarray(q_r), np.asarray(q_p))
    np.testing.assert_array_equal(
        np.asarray(hat_r, np.float32).view(np.uint8),
        np.asarray(hat_p, np.float32).view(np.uint8))
    assert int(jnp.max(q_p[:300])) <= 15 and int(jnp.max(q_p[300:600])) <= 3
    # masked leaf: q == 0 and hat untouched
    np.testing.assert_array_equal(np.asarray(q_p[600:]), 0)
    np.testing.assert_array_equal(np.asarray(hat_p[600:]),
                                  np.asarray(hat[600:]))


@pytest.mark.parametrize("radius_per_elem,levels_per_elem",
                         [(False, False), (True, False), (True, True)])
def test_quantize_q_only_matches_fused(radius_per_elem, levels_per_elem):
    """`quantize` (the trainer's sender kernel) writes exactly the q of
    `quantize_dequantize`, in each of the three variants."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(17), 3)
    n = 700
    theta = jax.random.normal(k1, (n,))
    hat = 0.5 * jax.random.normal(k2, (n,))
    u = jax.random.uniform(k3, (n,), jnp.float32)
    r = jnp.max(jnp.abs(theta - hat))
    radius = jnp.full((n,), r).at[600:].set(0.0) if radius_per_elem else r
    levels = (jnp.where(jnp.arange(n) < 300, 15.0, 3.0) if levels_per_elem
              else jnp.asarray(255.0))
    q_fused, _ = q_kernel.quantize_dequantize(theta, hat, u, radius, levels,
                                              interpret=True)
    q = q_kernel.quantize(theta, hat, u, radius, levels, interpret=True)
    assert q.dtype == jnp.uint8 and q.shape == (n,)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_fused))


@pytest.mark.parametrize("traced", [False, True])
def test_levels_of_is_exact(traced):
    """levels_of(b) == 2^b - 1 exactly, for every width the wire carries."""
    from repro.core.quantizer import levels_of
    bits = jnp.arange(1, 17, dtype=jnp.int32)
    lv = jax.jit(levels_of)(bits) if traced else levels_of(bits)
    assert lv.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(lv),
                                  2.0 ** np.arange(1, 17) - 1.0)
    assert float(levels_of(8)) == 255.0


SEGS = [  # (sizes, bits) mixed-width framing cases
    ((256,), (4,)),
    ((100, 200), (2, 8)),
    ((7, 0, 300, 65), (8, 4, 3, 5)),
    ((0, 0), (1, 8)),
    ((1000, 1, 129), (4, 1, 6)),
]


@pytest.mark.parametrize("sizes,bits", SEGS)
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_pack_mixed_roundtrip(sizes, bits, impl):
    """pack_mixed/unpack_mixed round-trip under the static (size, bits)
    framing, with mixed_packed_len as the wire-length contract; zero-size
    segments contribute no bytes (regression: the pack4 kernel divides by
    zero on an empty input)."""
    n = sum(sizes)
    key = jax.random.PRNGKey(n + 1)
    segs = []
    for i, (sz, b) in enumerate(zip(sizes, bits)):
        segs.append(jax.random.randint(jax.random.fold_in(key, i), (sz,),
                                       0, 2 ** b).astype(jnp.uint8))
    q = jnp.concatenate(segs) if segs else jnp.zeros((0,), jnp.uint8)
    pk = pack_ops.pack_mixed(q, sizes, bits, impl=impl)
    assert pk.size == pack_ops.mixed_packed_len(sizes, bits), (sizes, bits)
    un = pack_ops.unpack_mixed(pk, sizes, bits, impl=impl)
    np.testing.assert_array_equal(np.asarray(un), np.asarray(q))


@pytest.mark.parametrize("sizes,bits", SEGS)
def test_pack_mixed_impl_parity(sizes, bits):
    """ref and pallas produce byte-identical mixed wire buffers."""
    n = sum(sizes)
    q = jax.random.randint(jax.random.PRNGKey(n + 2), (n,), 0, 2).astype(
        jnp.uint8)
    pk_r = pack_ops.pack_mixed(q, sizes, bits, impl="ref")
    pk_p = pack_ops.pack_mixed(q, sizes, bits, impl="pallas")
    np.testing.assert_array_equal(np.asarray(pk_r), np.asarray(pk_p))


def test_mixed_packed_len_formula():
    """<=4-bit segments pay the pack4 nibble format (128*ceil(n/256) bytes),
    wider segments one byte per element, zero-size segments nothing."""
    assert pack_ops.mixed_packed_len((), ()) == 0
    assert pack_ops.mixed_packed_len((0,), (4,)) == 0
    assert pack_ops.mixed_packed_len((256,), (4,)) == 128
    assert pack_ops.mixed_packed_len((257,), (4,)) == 256
    assert pack_ops.mixed_packed_len((257,), (5,)) == 257
    assert pack_ops.mixed_packed_len((100, 200), (2, 8)) == 128 + 200


def test_kernel_block_shape_alignment():
    """Kernel tiles are (m,128) lane-aligned for every input size."""
    for n in (1, 127, 128, 129, 12345):
        theta = jnp.arange(n, dtype=jnp.float32)
        hat = jnp.zeros_like(theta)
        r = jnp.max(jnp.abs(theta))
        q, hat_new = q_kernel.quantize_dequantize(
            theta, hat, jnp.ones_like(theta), r, jnp.asarray(3.0), interpret=True
        )
        assert q.shape == theta.shape and hat_new.shape == theta.shape
