"""Operations and bytes of the decoder of window and full attention layers
over routed experts (`models/window_moe.py` config field names), computed
from shapes, by `opcount.py`'s rules: the mathematics, not what the program
executes.

A token is multiplied by its layer's attention projections at that KIND's
number of query heads (the per-head output gate among them), by the dense
layer's MLP or by the router, the shared expert and the routed experts it is
sent to THAT ARE HELD HERE (in expectation k x held / all: which pairs land
on a share is data, and no reader sees a step's live rows), and by the
lm_head. Attention at the scores each layer's rule KEEPS: a causal half in a
full layer, min(t + 1, window) keys for the query at t in a sliding one (so
its cost a token is flat in the sequence once the sequence is past the
window). No embedding gather, no recomputation under remat, no backward pass
through a share's router (`moe_layer`: a share's combine weights are
constants, its router is frozen). One multiply-add = 2 ops.
"""

from __future__ import annotations

from benchmarks.opcount import BF16, bound_seconds  # noqa: F401

FULL, SLIDING = "full_attention", "sliding_attention"


def held_layers(model: dict):
    """-> [(attention kind, query heads, feed-forward kind)] of the layers
    held, by the three per-layer lists."""
    held = model.get("layers")
    held = range(len(model["layer_types"])) if held is None else held
    return [(model["layer_types"][i], model["heads_per_layer"][i],
             model["mlp_layer_types"][i]) for i in held]


def attention_params(model: dict, heads: int) -> int:
    """q, k, v, o and the gate per head; no norm."""
    d, dh = model["d_model"], model["d_head"]
    return (2 * d * dh * (heads + model["n_kv_heads"])
            + (d * heads if model.get("attn_gate", True) else 0)
            + (2 * dh if model.get("qk_norm", False) else 0))


def _held(model: dict) -> int:
    return model.get("n_experts_held") or model["n_experts"]


def layer_params(model: dict, heads: int, mlp: str) -> int:
    """One layer held here, its two norms in."""
    d = model["d_model"]
    ffn = 3 * d * model["d_ff"] if mlp == "dense" else (
        d * model["n_experts"]
        + 3 * d * (_held(model) * model["d_ff_expert"] + model["d_ff_shared"]))
    return attention_params(model, heads) + 2 * d + ffn


def num_params(model: dict) -> int:
    """Layers + embedding + head + final norm."""
    d = model["d_model"]
    return (sum(layer_params(model, h, mlp)
                for _, h, mlp in held_layers(model))
            + 2 * model["vocab_size"] * d + d)


def kept_scores(seq: int, window=None) -> int:
    """Scores a (batch, head) keeps over `seq` positions: the causal half,
    diagonal in; within a window, min(t + 1, window) for the query at t."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_forward_flops(model: dict, seq: int, kind: str, heads: int,
                        mlp: str) -> dict:
    """Forward operations a token of one layer, by part."""
    d = model["d_model"]
    window = model["window"] if kind == SLIDING else None
    pairs = model["experts_per_token"] * _held(model) / model["n_experts"]
    parts = {
        "projections": 2 * (attention_params(model, heads)
                            - (2 * model["d_head"]
                               if model.get("qk_norm", False) else 0)),
        # QK^T and PV over d_head, all heads, this token's share
        "scores": 2 * 2 * heads * model["d_head"]
        * kept_scores(seq, window) / seq,
    }
    if mlp == "dense":
        parts["dense"] = 2 * 3 * d * model["d_ff"]
    else:
        parts["experts"] = 2 * (
            d * model["n_experts"] + 3 * d * (
                model["d_ff_shared"] + pairs * model["d_ff_expert"]))
    return parts


def forward_flops_by_part(model: dict, seq: int) -> dict:
    """Forward operations a token, by part; attention's scores by kind."""
    out = {"head": 2 * model["d_model"] * model["vocab_size"]}
    for kind, heads, mlp in held_layers(model):
        for part, ops in layer_forward_flops(model, seq, kind, heads,
                                             mlp).items():
            if part == "scores":
                part = "scores_" + ("window" if kind == SLIDING else "full")
            out[part] = out.get(part, 0) + ops
    return out


def forward_flops_per_token(model: dict, seq: int) -> float:
    return sum(forward_flops_by_part(model, seq).values())


def frozen_router_params(model: dict) -> int:
    """The routers of a share (fewer experts held than scored): they run
    forward and get no gradient. With every expert held the router trains."""
    if _held(model) == model["n_experts"]:
        return 0
    sparse = sum(mlp != "dense" for _, _, mlp in held_layers(model))
    return sparse * model["d_model"] * model["n_experts"]


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, less the backward (2 x forward) of
    a share's frozen routers."""
    return (3.0 * forward_flops_per_token(model, seq)
            - 2.0 * 2 * frozen_router_params(model))


def swa_flash_fwd(b: int, h: int, s: int, d: int, window: int,
                  kv_ratio: float = 1.0):
    """Flash forward under the window rule over [b, h, s, d] -> (ops,
    bytes). Ops: QK^T and PV at the KEPT scores. Bytes: read q, k, v (k, v
    at the model's kv heads), write o, all bf16."""
    ops = 2 * 2 * b * h * kept_scores(s, window) * d
    return ops, BF16 * b * s * d * (2 * h + 2 * h * kv_ratio)


def swa_flash_bwd(b: int, h: int, s: int, d: int, window: int,
                  kv_ratio: float = 1.0):
    """The backward pass (dq and dk/dv kernels together): the four matmuls
    the gradient needs (dV, dP, dQ, dK) at the kept scores; the recomputed
    QK^T is recomputation and not counted. Bytes: read q, k, v, o/do, write
    dq, dk, dv."""
    ops = 4 * 2 * b * h * kept_scores(s, window) * d
    return ops, BF16 * b * s * d * (4 * h + 4 * h * kv_ratio)
