"""Operations and bytes computed from a configuration's shapes.

`model_flops_per_round`: the FLOPs that one forward and one backward pass
of every worker over its batch require (backward = 2 x forward), counted
from the configuration's shapes: matrix products at 2 FLOPs per
multiply-add, causal self-attention over its lower triangle, the SSD in its
chunked form.  Gauss-Seidel's second pass over the inactive workers,
rematerialisation and elementwise work are not counted.

`codec_min_bytes_per_round`: the least HBM traffic of the quantizer in a
round, whatever implements it: read theta and the previous hat in float32
and write the levels at the wire width, for every worker that transmits in
a phase (each worker once per round).  Uniform draws are not counted, since
an in-kernel generator needs none.

`param_count` counts every parameter of the configuration's tree, as the
reference's `init` makes it.  The per-model counts live with each family's
reference (`reference/<family>.py`), found by the configuration's "family".
"""
from __future__ import annotations

from chip.reference import family


def param_count(cfg: dict) -> int:
    return family(cfg).param_count(cfg)


def forward_flops_per_sequence(cfg: dict, seq: int) -> int:
    return family(cfg).forward_flops_per_sequence(cfg, seq)


def model_flops_per_round(cfg: dict, traffic: dict) -> int:
    b = traffic["batch"]
    w = traffic["dist"]["num_workers"]
    return 3 * w * b["per_worker_batch"] * forward_flops_per_sequence(
        cfg, b["seq"])


def codec_min_bytes_per_round(cfg: dict, traffic: dict) -> float:
    dist = traffic["dist"]
    if not dist["quantize"]:
        return 0.0
    per_param = 8 + dist["bits"] / 8
    return dist["num_workers"] * param_count(cfg) * per_param
