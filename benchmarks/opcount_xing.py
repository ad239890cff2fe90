"""Operations and bytes of the Xing4.0 decoder (`models/mla_moe.py` config
field names with `hc_mult` > 0), computed from shapes, by `opcount.py`'s
rules: the mathematics, not what the program executes. The layer is
`opcount_joyai.py`'s (MLA, the dense MLP or the router, the shared expert
and the EXPECTED held pairs, the MTP block, the head twice; no embedding
gather, no recomputation, no backward pass through a share's frozen router)
on a residual path of n streams: two connections a layer, the MTP block's
too, each counted below. They are small (0.9 of ~400 MFLOP a token forward)
and they are counted. One multiply-add = 2 ops.

The path is bound by the HBM, not by its operations: `hc_bytes` is the least
a connection moves, and `hc_hbm_roofline` reads the path's device seconds
against it.
"""

from __future__ import annotations

from benchmarks.opcount import BF16, bound_seconds  # noqa: F401
from benchmarks import opcount_joyai
from benchmarks.opcount_joyai import (  # noqa: F401
    active_matmul_params,
    flash_bwd,
    flash_fwd,
    frozen_router_params,
    mla_matmul_params,
)


def n_maps(model: dict) -> int:
    n = model["hc_mult"]
    return n + n + n * n


def hc_connections(model: dict) -> int:
    """Connections a step runs: two a layer, the MTP block's layer too."""
    if not model.get("hc_mult"):
        return 0
    return 2 * (model["n_layers"] + model["mtp_depth"])


def hc_flops_per_token(model: dict) -> int:
    """One connection, forward, a token: the norm's sum of squares over the
    n D values (a multiply-add each), their projection on phi's n + n + n^2
    columns, the pre-mix (n D multiply-adds) and the post-mix (n^2 D for
    H_res X, n D for H_post y); Sinkhorn's 2 x iters x n^2 divisions and
    additions on 16 numbers are not matmul work and are left out."""
    n, d = model["hc_mult"], model["d_model"]
    return 2 * (n * d + n * d * n_maps(model) + n * d + (n * n + n) * d)


def train_flops_per_token(model: dict, seq: int) -> float:
    """`opcount_joyai.train_flops_per_token` + 3 x the connections' forward
    ops (forward + backward = 3 x forward)."""
    return (opcount_joyai.train_flops_per_token(model, seq)
            + 3.0 * hc_connections(model) * hc_flops_per_token(model))


def hc_bytes(model: dict, tokens: int) -> int:
    """The least bytes ONE connection moves over `tokens` tokens, forward
    and backward once each, no recomputation: forward reads X (n streams)
    and y, writes h and X'; backward reads the cotangents of X' and h,
    writes those of y and X: (4 n + 4) passes of [tokens, D] bf16. From the
    model's shape alone, so it is the same work whatever implements the
    path. NOT in it, though no implementation escapes them: the backward's
    second read of X and y (the maps' gradients are inner products of X with
    the cotangent of X'), and the maps themselves ([tokens, 24] float32: a
    thousandth of a stream). So 100% is not reachable: a connection that
    reads X once forward and once backward stands at (4 n + 4) / (5 n + 5) =
    80%."""
    n, d = model["hc_mult"], model["d_model"]
    return (4 * n + 4) * tokens * d * BF16
