"""Traffic generator: the batches a cell feeds its training rounds.

One general generator reads a traffic file (`workloads/<traffic>.json`,
key "batch") and the configuration, and builds from the seed a pool of
distinct host batches, shaped (W, per_worker_batch, ...):

  * tokens/labels: every worker owns a shard of Zipf(a)-distributed token
    ids over the configuration's vocabulary, and each batch row is a random
    window of seq + 1 tokens of that shard (labels are the tokens shifted
    by one).  Copied from the program's `data.synthetic.token_shards` and
    `data.pipeline.LMShardLoader`.
  * whatever else the configuration's family feeds per batch
    (`reference/<family>.py`'s `extra_inputs`: the encoder frames of an
    encoder-decoder).

The window cycles through the pool; the first rounds, which the
correctness check replays, see batches that all differ.
"""
from __future__ import annotations

import numpy as np

from chip.reference import family


def token_shards(n_workers: int, tokens_per_worker: int, vocab: int,
                 zipf_a: float, rng: np.random.Generator) -> np.ndarray:
    ranks = np.arange(1, vocab + 1)
    p = 1.0 / ranks ** zipf_a
    p /= p.sum()
    return rng.choice(vocab, size=(n_workers, tokens_per_worker),
                      p=p).astype(np.int32)


def batch_pool(cfg: dict, traffic: dict, seed: int) -> list[dict]:
    """`traffic["batch"]["pool"]` distinct host batches for this seed."""
    w = traffic["dist"]["num_workers"]
    spec = traffic["batch"]
    b, s = spec["per_worker_batch"], spec["seq"]
    extra = family(cfg).extra_inputs
    rng = np.random.default_rng(int(seed) % (1 << 64))
    n_tok = b * (s + 1) * spec["shard_windows"]
    shards = token_shards(w, n_tok, cfg["vocab"], spec["zipf_a"], rng)
    pool = []
    for _ in range(spec["pool"]):
        starts = rng.integers(0, n_tok - s - 1, size=(w, b))
        idx = starts[..., None] + np.arange(s + 1)[None, None]
        window = np.take_along_axis(
            shards, idx.reshape(w, b * (s + 1)), axis=1).reshape(w, b, s + 1)
        batch = {"tokens": window[..., :-1].copy(),
                 "labels": window[..., 1:].copy()}
        batch.update(extra(cfg, w, b, rng))
        pool.append(batch)
    return pool


def tokens_per_round(traffic: dict) -> int:
    """Decoder tokens of all workers in one round."""
    spec = traffic["batch"]
    return (traffic["dist"]["num_workers"] * spec["per_worker_batch"]
            * spec["seq"])
