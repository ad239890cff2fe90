"""Operations and bytes of the latent-attention, routed-experts decoder
(`models/mla_moe.py` config field names), computed from shapes, by
`opcount.py`'s rules: the mathematics, not what the program executes. A
token is multiplied by MLA's projections, by the dense layers' MLP or by the
router, the shared expert and the routed experts it is sent to THAT ARE
HELD HERE (in expectation k x held / all: which pairs land on a share is
data, and no reader sees a step's live rows), by the MTP block and by the
lm_head twice (the MTP block predicts through it too); no embedding gather,
no recomputation under remat, no backward pass through a share's router
(`moe_layer`: a share's combine weights are constants, its router is frozen), causal attention at its causal half, over
`qk_nope_head_dim + qk_rope_head_dim` channels for the scores and
`v_head_dim` for the values. One multiply-add = 2 ops.
"""

from __future__ import annotations

from benchmarks.opcount import BF16, bound_seconds  # noqa: F401


def mla_matmul_params(model: dict) -> int:
    d, h = model["d_model"], model["n_heads"]
    d_qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return (d * model["q_lora_rank"] + model["q_lora_rank"] * h * d_qk
            + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            + model["kv_lora_rank"] * h
            * (model["qk_nope_head_dim"] + model["v_head_dim"])
            + h * model["v_head_dim"] * d)


def expert_layer_active_matmul_params(model: dict) -> float:
    """MLA, router, shared expert, and the EXPECTED held pairs of a token:
    experts_per_token x n_experts_held / n_experts experts."""
    d = model["d_model"]
    one_expert = 3 * d * model["d_ff_expert"]
    held_pairs = (model["experts_per_token"] * model["n_experts_held"]
                  / model["n_experts"])
    return (mla_matmul_params(model) + d * model["n_experts"]
            + (model["n_shared_experts"] + held_pairs) * one_expert)


def active_matmul_params(model: dict) -> float:
    d = model["d_model"]
    n_dense = model["n_dense_layers"]
    dense = mla_matmul_params(model) + 3 * d * model["d_ff"]
    expert = expert_layer_active_matmul_params(model)
    head = d * model["vocab_size"]
    mtp = model["mtp_depth"] * (2 * d * d + expert + head)
    return (n_dense * dense + (model["n_layers"] - n_dense) * expert
            + head + mtp)


def frozen_router_params(model: dict) -> int:
    """The routers of a share (fewer experts held than scored): they run
    forward and get no gradient, so the backward pass has no matmul of
    theirs. With every expert held the router trains: 0."""
    if model["n_experts_held"] == model["n_experts"]:
        return 0
    routed_blocks = (model["n_layers"] - model["n_dense_layers"]
                     + model["mtp_depth"])
    return routed_blocks * model["d_model"] * model["n_experts"]


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, less the backward (2 x forward) of
    a share's frozen routers. Forward: 2 ops per active weight, plus per
    attention layer (the MTP block has one) causal QK^T over the 192 score
    channels and PV over the 128 value channels, on average seq/2 keys."""
    attn = model["n_heads"] * seq * (
        model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
        + model["v_head_dim"])
    layers = model["n_layers"] + model["mtp_depth"]
    return (3.0 * (2 * active_matmul_params(model) + layers * attn)
            - 2.0 * 2 * frozen_router_params(model))


def flash_fwd(b: int, h: int, s: int, d_qk: int, d_v: int):
    """Causal flash forward, q, k [b, h, s, d_qk], v [b, h, s, d_v] ->
    (ops, bytes). Ops: QK^T over d_qk and PV over d_v at the causal half.
    Bytes: read q, k, v, write o, all bf16."""
    ops = 2 * b * h * s * s * (d_qk + d_v) / 2
    return ops, BF16 * b * h * s * (2 * d_qk + 2 * d_v)


def flash_bwd(b: int, h: int, s: int, d_qk: int, d_v: int):
    """Causal flash backward (dq and dk/dv kernels together): dP and dV over
    d_v, dQ and dK over d_qk; the recomputed QK^T is recomputation and not
    counted. Bytes: read q, k, v, o, do, write dq, dk, dv."""
    ops = 2 * 2 * b * h * s * s * (d_qk + d_v) / 2
    return ops, BF16 * b * h * s * (4 * d_qk + 4 * d_v)
