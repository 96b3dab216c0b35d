"""Plain reference of the Mamba2 language model, in jax.numpy.

Written from the Mamba2 description (arXiv:2405.21060): per layer an
RMSNorm, the projections to z, x, B, C and dt, a causal depthwise
convolution with SiLU over (x, B, C), the SSD recurrence
h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T, y_t = C_t h_t + D x_t computed
by the paper's minimal chunked form (`ssd_minimal_discrete`), the gated
RMSNorm of y * silu(z) and the output projection; the adaptations that the
configuration file lists apply.  It imports nothing of the program.  Every
operation runs in the dtype of the parameters it is given.

`init` makes weights from a key in the parameter tree the trainer expects.
`param_count`, `forward_flops_per_sequence`, `extra_inputs` (none) and the
smoke sizes `TINY`, `TINY_BATCH` are what the harness needs of the family.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .audio import layer, rmsnorm


def init(key, cfg: dict, dtype=jnp.float32) -> dict:
    s = cfg["ssm"]
    d, v, n = cfg["d_model"], cfg["vocab"], cfg["n_layers"]
    di = s["expand"] * d
    nh = di // s["head_dim"]
    gn = s["n_groups"] * s["state_dim"]
    conv_ch = di + 2 * gn
    keys = iter(jax.random.split(key, 32))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def zeros(*shape):
        return jnp.zeros(shape, dtype)

    a_log = jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32))
    return {
        "embed": {"tok": normal((v, d), 0.02), "unembed": normal((d, v), 0.02),
                  "ln_f": zeros(d)},
        "blocks": {
            "ln": zeros(n, d),
            "mamba": {
                "w_z": normal((n, d, di), d ** -0.5),
                "w_x": normal((n, d, di), d ** -0.5),
                "w_b": normal((n, d, gn), d ** -0.5),
                "w_c": normal((n, d, gn), d ** -0.5),
                "w_dt": normal((n, d, nh), d ** -0.5),
                "conv_w": normal((n, s["conv_width"], conv_ch), 0.1),
                "conv_b": zeros(n, conv_ch),
                "a_log": jnp.tile(a_log[None], (n, 1)).astype(dtype),
                "d_skip": jnp.ones((n, nh), dtype),
                "dt_bias": zeros(n, nh),
                "norm": zeros(n, di),
                "out_proj": normal((n, di, d), di ** -0.5),
            },
        },
    }


def segsum(x):
    """x (..., T) -> (..., T, T): sum of x[j+1..i] below the diagonal,
    -inf above it."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool))
    return jnp.where(mask, seg, jnp.asarray(-jnp.inf, x.dtype))


def ssd(x, a, b, c, block: int):
    """x (B, L, H, P) already scaled by dt; a (B, L, H) = dt * A;
    b, c (B, L, H, N).  The chunked minimal SSD of the Mamba2 paper."""
    bs, length, h, p = x.shape
    nc = length // block

    def chunks(t):
        return t.reshape((bs, nc, block) + t.shape[2:])

    x, a, b, c = chunks(x), chunks(a), chunks(b), chunks(c)
    a = jnp.moveaxis(a, -1, 1)                                # (B, H, C, L)
    a_cs = jnp.cumsum(a, axis=-1)
    decay = jnp.exp(segsum(a))                                # (B,H,C,L,L)
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", c, b, decay, x)
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", b, decay_states, x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    chunk_a = jnp.pad(a_cs[..., -1], ((0, 0), (0, 0), (1, 0)))
    decay_chunk = jnp.exp(segsum(chunk_a))                    # (B,H,C+1,C+1)
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cs))
    return (y_diag + y_off).reshape(bs, length, h, p)


def mamba(p, u, cfg: dict):
    s = cfg["ssm"]
    di = s["expand"] * cfg["d_model"]
    nh = di // s["head_dim"]
    g, n = s["n_groups"], s["state_dim"]
    bs, length, _ = u.shape
    z = u @ p["w_z"]
    xbc = jnp.concatenate([u @ p["w_x"], u @ p["w_b"], u @ p["w_c"]], -1)
    width = p["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + length] * p["conv_w"][i]
               for i in range(width))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x = xbc[..., :di].reshape(bs, length, nh, s["head_dim"])
    b = xbc[..., di:di + g * n].reshape(bs, length, g, n)
    c = xbc[..., di + g * n:].reshape(bs, length, g, n)
    b = jnp.repeat(b, nh // g, axis=2)
    c = jnp.repeat(c, nh // g, axis=2)
    dt = jax.nn.softplus(u @ p["w_dt"] + p["dt_bias"])        # (B, L, H)
    a = -jnp.exp(p["a_log"])
    y = ssd(x * dt[..., None], dt * a, b, c, s["chunk"])
    y = y + x * p["d_skip"][:, None]
    y = y.reshape(bs, length, di) * jax.nn.silu(z)
    return rmsnorm(y, p["norm"], cfg["rms_eps"]) @ p["out_proj"]


def loss(params, batch, cfg: dict):
    """Mean next-token cross-entropy of one worker's batch."""
    eps = cfg["rms_eps"]
    x = params["embed"]["tok"][batch["tokens"]]
    for i in range(cfg["n_layers"]):
        blk = layer(params["blocks"], i)
        x = x + mamba(blk["mamba"], rmsnorm(x, blk["ln"], eps), cfg)
    logits = rmsnorm(x, params["embed"]["ln_f"], eps) @ params["embed"]["unembed"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)
    return -jnp.mean(picked.astype(jnp.float32))


# ---------------------------------------------------------- harness --
TINY = dict(n_layers=2, d_model=64, vocab=137,
            ssm=dict(state_dim=16, head_dim=16, chunk=8))
TINY_BATCH = dict(per_worker_batch=2, seq=32)


def extra_inputs(cfg: dict, w: int, b: int, rng) -> dict:
    return {}


def _widths(cfg: dict):
    s = cfg["ssm"]
    di = s["expand"] * cfg["d_model"]
    return s, di, di // s["head_dim"], s["n_groups"] * s["state_dim"]


def param_count(cfg: dict) -> int:
    """Every parameter of `init`'s tree."""
    d, v = cfg["d_model"], cfg["vocab"]
    s, di, nh, gn = _widths(cfg)
    conv_ch = di + 2 * gn
    layer_ = (d * (2 * di + 2 * gn + nh) + (s["conv_width"] + 1) * conv_ch
              + 3 * nh + di + di * d + d)
    return 2 * v * d + d + cfg["n_layers"] * layer_


def not_in_num_params(cfg: dict) -> int:
    """Parameters that the program's `num_params` leaves out: the final norm,
    and each layer's conv bias of (x, B, C) and dt bias."""
    _, di, nh, gn = _widths(cfg)
    return cfg["d_model"] + cfg["n_layers"] * (di + 2 * gn + nh)


def forward_flops_per_sequence(cfg: dict, seq: int) -> int:
    """Matrix-product FLOPs of one forward pass over one sequence, the SSD
    in its chunked form."""
    d, v = cfg["d_model"], cfg["vocab"]
    s, di, nh, gn = _widths(cfg)
    p, n, g, q = s["head_dim"], s["state_dim"], s["n_groups"], s["chunk"]
    nc = -(-seq // q)
    proj = 2 * seq * d * (2 * di + 2 * gn + nh) + 2 * seq * di * d
    conv = 2 * seq * (di + 2 * gn) * s["conv_width"]
    ssd_ = nc * (2 * q * q * g * n        # C B^T per chunk
                 + 2 * q * q * nh * p     # masked-decay scores times x
                 + 2 * q * n * nh * p     # chunk states
                 + 2 * q * n * nh * p)    # state contribution to y
    return 2 * seq * d * v + cfg["n_layers"] * (proj + conv + ssd_)
