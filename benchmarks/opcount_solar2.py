"""Operations and bytes of the gated-GQA / KDA pattern decoder
(`models/solar_open2.py` config field names), computed from shapes, by
`opcount.py`'s rules: the mathematics, not what the program executes. A
token is multiplied by its layer's mixer (GQA's four projections and its
gate; or KDA's four projections, its two low-rank gate pairs and its beta),
by the router, the shared expert and the routed experts it is sent to THAT
ARE HELD HERE (in expectation k x held / all), and by the lm_head; no
embedding gather, no recomputation under remat, no backward pass through a
share's router (`moe_layer`: a share's combine weights are constants).
Causal attention at its causal half over `d_head` channels, 64 query heads.
The delta rule is counted in its CHUNKED form, the DEFINITION's matmuls
(`opcount_ling.kda_chunk_ops`: chunk 64, two score matrices, the triangular
inverse by substitution, U, W, W H, Q H, B U~, the state's update), whatever
plan of `ops/kda.py` forms the scores: the any-decay plan's six masked
products where the bounded plan has four are the implementation's, as the
solve's exact products are. One multiply-add = 2 ops.
"""

from __future__ import annotations

from benchmarks.opcount import (  # noqa: F401
    BF16,
    bound_seconds,
    flash_bwd,
    flash_fwd,
)
from benchmarks.opcount_ling import (  # noqa: F401
    CHUNK,
    kda_bwd,
    kda_chunk_ops,
    kda_fwd,
)


def _layers(model: dict) -> list:
    held = model.get("layers")
    return list(range(model["n_layers_published"])) if held is None \
        else list(held)


def _is_full(model: dict, i: int) -> bool:
    return i % model.get("period", 4) == model.get("full_phase", 0)


def _gate_rank(model: dict) -> int:
    return model.get("kda_gate_rank", 128)


def gqa_params(model: dict) -> int:
    """W_q, W_k, W_v, W_o and the output gate a channel: all of them
    matmuls."""
    d, hd = model["d_model"], model["n_heads"] * model["d_head"]
    return d * (3 * hd + 2 * model["n_kv_heads"] * model["d_head"])


def kda_matmul_params(model: dict) -> int:
    """W_q, W_k, W_v, W_o, the decay's and the output gate's low-rank
    pairs, w_b."""
    d, hd = model["d_model"], model["n_heads"] * model["kda_head_dim"]
    r = _gate_rank(model)
    return 4 * d * hd + 2 * (d * r + r * hd) + d * model["n_heads"]


def kda_params(model: dict) -> int:
    """`kda_matmul_params` + three conv filters, A_log, dt_bias, the head
    norm."""
    hd = model["n_heads"] * model["kda_head_dim"]
    return (kda_matmul_params(model) + 3 * model.get("conv_size", 4) * hd
            + model["n_heads"] + hd + model["kda_head_dim"])


def routed_params(model: dict) -> int:
    """Router and bias, the held experts, the shared expert."""
    d = model["d_model"]
    return (d * model["n_experts"] + model["n_experts"]
            + 3 * d * model["d_ff_expert"]
            * (model["n_experts_held"] + model.get("n_shared_experts", 1)))


def num_params(model: dict) -> int:
    """What the program holds: the held layers (a mixer, two layer norms,
    the routed part), embedding, head, final norm."""
    d = model["d_model"]
    total = 2 * model["vocab_size"] * d + d
    for i in _layers(model):
        total += (gqa_params(model) if _is_full(model, i)
                  else kda_params(model)) + 2 * d + routed_params(model)
    return total


def _kda_token_ops(model: dict) -> float:
    """Forward ops a token of one KDA mixer: 2 a weight of the projections,
    the conv's taps, the chunked delta rule's matmuls."""
    hd = model["n_heads"] * model["kda_head_dim"]
    d = model["kda_head_dim"]
    return (2 * (kda_matmul_params(model)
                 + 3 * model.get("conv_size", 4) * hd)
            + model["n_heads"] * kda_chunk_ops(d, d) / CHUNK)


def _gqa_token_ops(model: dict, seq: int) -> float:
    """2 a weight, and the causal half of QK^T and PV: 2 x 2 x H x d x
    seq / 2."""
    return 2 * gqa_params(model) \
        + 2 * model["n_heads"] * model["d_head"] * seq


def _routed_token_ops(model: dict) -> float:
    held_pairs = (model["experts_per_token"] * model["n_experts_held"]
                  / model["n_experts"])
    return 2 * (model["d_model"] * model["n_experts"]
                + (model.get("n_shared_experts", 1) + held_pairs)
                * 3 * model["d_model"] * model["d_ff_expert"])


def frozen_router_params(model: dict) -> int:
    """As `opcount_joyai.frozen_router_params`: the routers of a share run
    forward and get no gradient."""
    if model["n_experts_held"] == model["n_experts"]:
        return 0
    return len(_layers(model)) * model["d_model"] * model["n_experts"]


def forward_flops_per_token(model: dict, seq: int) -> float:
    total = 2.0 * model["d_model"] * model["vocab_size"]
    for i in _layers(model):
        total += _gqa_token_ops(model, seq) if _is_full(model, i) \
            else _kda_token_ops(model)
        total += _routed_token_ops(model)
    return total


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, less the backward (2 x forward) of
    a share's frozen routers."""
    return 3.0 * forward_flops_per_token(model, seq) \
        - 2.0 * 2 * frozen_router_params(model)
