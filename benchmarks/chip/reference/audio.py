"""Plain reference of the whisper-style encoder-decoder, in jax.numpy.

Written from the whisper description (arXiv:2212.04356) with the
adaptations that the configuration file lists under "adaptations"; it
imports nothing of the program.  Every operation runs in the dtype of the
parameters it is given: float32 (at "highest" matmul precision, set by the
caller) for the reference, bfloat16 for the control.

`init` makes weights from a key in the parameter tree the trainer expects
(the same keys and shapes), so the benchmark feeds both the program and
this reference from one seed.

Beside the model, what the harness needs of the family (found by the
configuration's "family"): `param_count`, `forward_flops_per_sequence`
(counts.py), `extra_inputs` (traffic.py: the encoder frames), and the
smoke sizes `TINY` and `TINY_BATCH` that the CPU tests run at.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, cfg: dict, dtype=jnp.float32) -> dict:
    d, h, ff, v = cfg["d_model"], cfg["n_heads"], cfg["d_ff"], cfg["vocab"]
    dh = d // h
    n_dec, n_enc = cfg["n_layers"], cfg["encoder_layers"]
    keys = iter(jax.random.split(key, 32))

    def normal(shape, scale):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def attn(n):
        return {"wq": normal((n, d, h, dh), d ** -0.5),
                "wk": normal((n, d, h, dh), d ** -0.5),
                "wv": normal((n, d, h, dh), d ** -0.5),
                "wo": normal((n, h, dh, d), d ** -0.5)}

    def mlp(n):
        return {"w_up": normal((n, d, ff), d ** -0.5),
                "w_down": normal((n, ff, d), ff ** -0.5)}

    def zeros(*shape):
        return jnp.zeros(shape, dtype)

    return {
        "embed": {"tok": normal((v, d), 0.02), "unembed": normal((d, v), 0.02),
                  "ln_f": zeros(d)},
        "encoder": {"attn": attn(n_enc), "mlp": mlp(n_enc),
                    "ln1": zeros(n_enc, d), "ln2": zeros(n_enc, d)},
        "enc_ln_f": zeros(d),
        "decoder": {"attn": attn(n_dec), "xattn": attn(n_dec),
                    "mlp": mlp(n_dec), "ln1": zeros(n_dec, d),
                    "lnx": zeros(n_dec, d), "ln2": zeros(n_dec, d)},
    }


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1 + scale)


def rope(x, theta):
    """Rotate-half RoPE over positions 0..S-1; x (B, S, H, Dh)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, xq, xkv, causal: bool, rope_theta=None):
    q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xkv, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xkv, p["wv"])
    if rope_theta is not None:
        q, k = rope(q, rope_theta), rope(k, rope_theta)
    dh = q.shape[-1]
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) / jnp.sqrt(
        jnp.asarray(dh, q.dtype))
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, scores.dtype))
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshk->bqhk", w, v)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"])


def mlp(p, x):
    return jax.nn.gelu(x @ p["w_up"], approximate=True) @ p["w_down"]


def sinusoid(n: int, d: int):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None]
    ang = pos / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def loss(params, batch, cfg: dict):
    """Mean next-token cross-entropy of one worker's batch."""
    dt = params["embed"]["tok"].dtype
    eps = cfg["rms_eps"]
    frames = batch["frames"].astype(dt)
    x = frames + sinusoid(frames.shape[1], cfg["d_model"]).astype(dt)[None]
    for i in range(cfg["encoder_layers"]):
        blk = layer(params["encoder"], i)
        h = rmsnorm(x, blk["ln1"], eps)
        x = x + attention(blk["attn"], h, h, causal=False)
        x = x + mlp(blk["mlp"], rmsnorm(x, blk["ln2"], eps))
    enc = rmsnorm(x, params["enc_ln_f"], eps)

    y = params["embed"]["tok"][batch["tokens"]]
    for i in range(cfg["n_layers"]):
        blk = layer(params["decoder"], i)
        h = rmsnorm(y, blk["ln1"], eps)
        y = y + attention(blk["attn"], h, h, causal=True,
                          rope_theta=cfg["rope_theta"])
        y = y + attention(blk["xattn"], rmsnorm(y, blk["lnx"], eps), enc,
                          causal=False)
        y = y + mlp(blk["mlp"], rmsnorm(y, blk["ln2"], eps))
    logits = rmsnorm(y, params["embed"]["ln_f"], eps) @ params["embed"]["unembed"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)
    return -jnp.mean(picked.astype(jnp.float32))


# ---------------------------------------------------------- harness --
TINY = dict(n_layers=2, encoder_layers=2, d_model=64, n_heads=4,
            n_kv_heads=4, d_ff=128, vocab=139, encoder_frames=32)
TINY_BATCH = dict(per_worker_batch=2, seq=16)


def extra_inputs(cfg: dict, w: int, b: int, rng) -> dict:
    """Per-batch encoder frame embeddings, standard normal."""
    return {"frames": rng.standard_normal(
        (w, b, cfg["encoder_frames"], cfg["d_model"]), dtype="float32")}


def _attn_proj(d: int, h: int, dh: int) -> int:
    return 4 * d * h * dh


def param_count(cfg: dict) -> int:
    """Every parameter of `init`'s tree."""
    d, v, h, ff = cfg["d_model"], cfg["vocab"], cfg["n_heads"], cfg["d_ff"]
    attn = _attn_proj(d, h, d // h)
    enc = attn + 2 * d * ff + 2 * d
    dec = 2 * attn + 2 * d * ff + 3 * d
    return (2 * v * d + d + cfg["encoder_layers"] * enc + d
            + cfg["n_layers"] * dec)


def not_in_num_params(cfg: dict) -> int:
    """Parameters that the program's `num_params` leaves out: the two final
    norms."""
    return 2 * cfg["d_model"]


def forward_flops_per_sequence(cfg: dict, seq: int) -> int:
    """Matrix-product FLOPs of one forward pass over one sequence."""
    d, v, h = cfg["d_model"], cfg["vocab"], cfg["n_heads"]
    ff, t = cfg["d_ff"], cfg["encoder_frames"]
    dh = d // h
    enc = 2 * t * _attn_proj(d, h, dh) + 4 * t * t * h * dh + 4 * t * d * ff
    causal = seq * (seq + 1) // 2
    dec = (2 * seq * _attn_proj(d, h, dh) + 4 * causal * h * dh    # self
           + 2 * seq * 2 * d * h * dh + 2 * t * 2 * d * h * dh     # cross q,o / k,v
           + 4 * seq * t * h * dh                                  # cross scores
           + 4 * seq * d * ff)
    return (2 * seq * d * v + cfg["encoder_layers"] * enc
            + cfg["n_layers"] * dec)
