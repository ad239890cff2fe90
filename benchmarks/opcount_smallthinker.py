"""Operations and bytes of SmallThinker-21BA3B as `models/window_moe.py`
runs it (its config field names), by `opcount_laguna.py`'s rules, which
already count a model with no attention gate (`attn_gate` false), no dense
layer and no shared expert (`d_ff_shared` 0): the mathematics, not what the
program executes. What the router READS (the attention's input or the
feed-forward's) moves no operation, and relu for silu none that is counted.

Adds the full (causal) layers' flash call under the signature
`window_readers.flash_roofline` calls, so that one trace's two kinds of
call, told apart by the window rule's scope in their names, are each read
at the scores their rule keeps.
"""

from __future__ import annotations

from benchmarks import opcount_laguna
from benchmarks.opcount_laguna import (  # noqa: F401
    BF16,
    FULL,
    SLIDING,
    bound_seconds,
    kept_scores,
    swa_flash_bwd,
    swa_flash_fwd,
)


def named(model: dict) -> dict:
    """`model` with `layer_types` as the kinds' names, which
    `opcount_laguna` compares: this configuration hands the program the
    published 0 / 1 of `sliding_window_layout` (1: a window layer)."""
    return dict(model, layer_types=[
        {0: FULL, 1: SLIDING}.get(t, t) for t in model["layer_types"]])


def num_params(model: dict) -> int:
    return opcount_laguna.num_params(named(model))


def forward_flops_by_part(model: dict, seq: int) -> dict:
    return opcount_laguna.forward_flops_by_part(named(model), seq)


def forward_flops_per_token(model: dict, seq: int) -> float:
    return opcount_laguna.forward_flops_per_token(named(model), seq)


def train_flops_per_token(model: dict, seq: int) -> float:
    """3 x forward, less the backward of a share's frozen routers."""
    return opcount_laguna.train_flops_per_token(named(model), seq)


def full_flash_fwd(b: int, h: int, s: int, d: int, window=None,
                   kv_ratio: float = 1.0):
    """Flash forward under `CAUSAL` over [b, h, s, d] -> (ops, bytes): QK^T
    and PV at the causal half, diagonal in (`window`, the model's, is the
    other kind's and is not read). Bytes as `swa_flash_fwd`."""
    return swa_flash_fwd(b, h, s, d, None, kv_ratio)


def full_flash_bwd(b: int, h: int, s: int, d: int, window=None,
                   kv_ratio: float = 1.0):
    """The backward pass (dq and dk/dv together) under `CAUSAL`: dV, dP, dQ,
    dK at the causal half; the recomputed QK^T is not counted."""
    return swa_flash_bwd(b, h, s, d, None, kv_ratio)
