"""Operations and bytes of the pattern decoder (`models/hybrid_moe.py`
config field names: KDA linear-attention and MLA layers to a period,
experts chosen within groups), computed from shapes, by `opcount.py`'s
rules: the mathematics, not what the program executes. A token is
multiplied by its layer's mixer (KDA's six projections and its beta; or
MLA's), by the dense layers' MLP or by the router, the shared expert and
the routed experts it is sent to THAT ARE HELD HERE (in expectation k x
held / all), and by the lm_head; no embedding gather, no recomputation
under remat, no backward pass through a share's router (`moe_layer`: a
share's combine weights are constants). Causal attention at its causal
half over `qk_nope_head_dim + qk_rope_head_dim` score channels and
`v_head_dim` value channels. The delta rule is counted in its CHUNKED form
(`ops/kda.py`, chunk 64): that is the algorithm whose matmuls run; the
token-by-token recurrence would be 7 d_k d_v ops a token and head, 0.6x
of it. One multiply-add = 2 ops.
"""

from __future__ import annotations

from benchmarks.opcount import BF16, bound_seconds  # noqa: F401
from benchmarks.opcount_joyai import flash_bwd, flash_fwd  # noqa: F401

F32 = 4
CHUNK = 64


def _layers(model: dict) -> list:
    held = model.get("layers")
    return list(range(model["n_layers_published"])) if held is None \
        else list(held)


def _is_mla(model: dict, i: int) -> bool:
    return (i + 1) % model["period"] == 0


def kda_params(model: dict) -> int:
    """W_q, W_k, W_v, W_f, W_g, W_o, w_b, three conv filters, A_log,
    dt_bias, the head norm."""
    hd = model["n_heads"] * model["kda_head_dim"]
    return (6 * model["d_model"] * hd + model["d_model"] * model["n_heads"]
            + 3 * model["conv_size"] * hd + model["n_heads"] + hd
            + model["kda_head_dim"])


def mla_params(model: dict) -> int:
    """W_q (no latent), W_kva, the kv latent's norm, W_kvb, W_o, the
    head-wise gate, the two per-head QK-norm scales."""
    d, h = model["d_model"], model["n_heads"]
    d_qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return (d * h * d_qk + d * (model["kv_lora_rank"]
                                + model["qk_rope_head_dim"])
            + model["kv_lora_rank"] + model["kv_lora_rank"] * h
            * (model["qk_nope_head_dim"] + model["v_head_dim"])
            + h * model["v_head_dim"] * d + d * h + 2 * d_qk)


def routed_params(model: dict) -> int:
    """Router and bias, the held experts, the shared expert."""
    d = model["d_model"]
    return (d * model["n_experts"] + model["n_experts"]
            + 3 * d * model["d_ff_expert"]
            * (model["n_experts_held"] + model["n_shared_experts"]))


def num_params(model: dict) -> int:
    """What the program holds: the held layers (a mixer, two layer norms,
    an MLP or the routed part), embedding, head, final norm."""
    d = model["d_model"]
    total = 2 * model["vocab_size"] * d + d
    for i in _layers(model):
        total += (mla_params(model) if _is_mla(model, i)
                  else kda_params(model)) + 2 * d
        total += 3 * d * model["d_ff"] if i < model["n_dense_layers"] \
            else routed_params(model)
    return total


def kda_chunk_ops(d_k: int, d_v: int, chunk: int = CHUNK) -> float:
    """The chunked form's matmuls for one chunk of one head, forward: the
    two score matrices (K K^T and Q K^T under the decay), the triangular
    inverse by substitution (chunk^3 / 3 multiply-adds), U = T V and
    W = T K, W H, Q H, B U~ and the state's K^T U~."""
    c = chunk
    return (2 * c * c * (3 * d_k + 2 * d_v) + 3 * 2 * c * d_k * d_v
            + 2 * c ** 3 / 3)


def kda_fwd(b: int, h: int, s: int, d_k: int, d_v: int):
    """`ops/kda.py` forward over q, k [b, h, s, d_k], v [b, h, s, d_v] ->
    (ops, bytes). Bytes, each operand once: q, k, v read and o written in
    bf16, the log decay g [b, h, s, d_k] and beta [b, h, s] read in
    float32, the final state written."""
    chunks = b * h * -(-s // CHUNK)
    nbytes = b * h * (s * (BF16 * 2 * (d_k + d_v) + F32 * (d_k + 1))
                      + F32 * d_k * d_v)
    return chunks * kda_chunk_ops(d_k, d_v), nbytes


def kda_bwd(b: int, h: int, s: int, d_k: int, d_v: int):
    """The backward pass: two matmuls for each of the forward's (the solve's
    too); the recomputed forward is recomputation and not counted. Bytes:
    q, k, v, g, beta and do read, dq, dk, dv (bf16), dg and dbeta (float32)
    written."""
    chunks = b * h * -(-s // CHUNK)
    nbytes = b * h * s * (BF16 * (2 * d_k + 2 * d_v) + F32 * (d_k + 1)
                          + BF16 * (2 * d_k + d_v) + F32 * (d_k + 1))
    return 2 * chunks * kda_chunk_ops(d_k, d_v), nbytes


def _kda_token_ops(model: dict) -> float:
    """Forward ops a token of one KDA mixer: 2 a weight of the projections,
    the conv's taps, the chunked delta rule's matmuls."""
    hd = model["n_heads"] * model["kda_head_dim"]
    d = model["kda_head_dim"]
    return (2 * (6 * model["d_model"] * hd
                 + model["d_model"] * model["n_heads"]
                 + 3 * model["conv_size"] * hd)
            + model["n_heads"] * kda_chunk_ops(d, d) / CHUNK)


def _mla_token_ops(model: dict, seq: int) -> float:
    d, h = model["d_model"], model["n_heads"]
    d_qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    weights = (d * h * d_qk
               + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
               + model["kv_lora_rank"] * h
               * (model["qk_nope_head_dim"] + model["v_head_dim"])
               + h * model["v_head_dim"] * d + d * h)
    return 2 * weights + h * seq * (d_qk + model["v_head_dim"])


def _routed_token_ops(model: dict) -> float:
    held_pairs = (model["experts_per_token"] * model["n_experts_held"]
                  / model["n_experts"])
    return 2 * (model["d_model"] * model["n_experts"]
                + (model["n_shared_experts"] + held_pairs)
                * 3 * model["d_model"] * model["d_ff_expert"])


def frozen_router_params(model: dict) -> int:
    """As `opcount_joyai.frozen_router_params`: the routers of a share run
    forward and get no gradient."""
    if model["n_experts_held"] == model["n_experts"]:
        return 0
    routed = sum(i >= model["n_dense_layers"] for i in _layers(model))
    return routed * model["d_model"] * model["n_experts"]


def forward_flops_per_token(model: dict, seq: int) -> float:
    total = 2.0 * model["d_model"] * model["vocab_size"]
    for i in _layers(model):
        total += _mla_token_ops(model, seq) if _is_mla(model, i) \
            else _kda_token_ops(model)
        total += 2 * 3 * model["d_model"] * model["d_ff"] \
            if i < model["n_dense_layers"] else _routed_token_ops(model)
    return total


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, less the backward (2 x forward) of
    a share's frozen routers."""
    return 3.0 * forward_flops_per_token(model, seq) \
        - 2.0 * 2 * frozen_router_params(model)
