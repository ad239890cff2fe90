"""Operations and bytes the algorithms need, computed from shapes.

`model` is the "model" object of a configuration file (LlamaConfig field
names). Counts are of the mathematics, not of what the program executes:
no embedding gather (a lookup is no matmul), no recomputation under remat,
causal attention counted at its causal half. One multiply-add = 2 ops.
"""

from __future__ import annotations

BF16 = 2  # bytes


def layer_matmul_params(model: dict) -> int:
    d, h, kv, dh, ff = (model[k] for k in
                        ("d_model", "n_heads", "n_kv_heads", "d_head", "d_ff"))
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * ff


def matmul_params(model: dict) -> int:
    """Weights every token is multiplied by: the layers and the lm_head.
    The embedding table is gathered from, not multiplied."""
    return (model["n_layers"] * layer_matmul_params(model)
            + model["d_model"] * model["vocab_size"])


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward = 3 x forward. Forward: 2 ops per weight, plus
    causal attention's two matmuls (QK^T, PV) over on average seq/2 keys:
    2 * 2 * H * Dh * seq/2 per layer."""
    attn = 2 * model["n_heads"] * model["d_head"] * seq
    return 3.0 * (2 * matmul_params(model) + model["n_layers"] * attn)


def weight_bytes(model: dict) -> int:
    """Bytes of the weights one decode step has to read (bf16), norms and
    embedding rows left out; KV reads are NOT in here (see the metric's
    file)."""
    return BF16 * matmul_params(model)


def flash_fwd(b: int, h: int, s: int, d: int, kv_ratio: float = 1.0):
    """Causal flash forward over [b, h, s, d] -> (ops, bytes). Ops: QK^T
    and PV at the causal half. Bytes: read q, k, v (k, v at the model's kv
    heads), write o, all bf16."""
    ops = 2 * 2 * b * h * s * s * d / 2
    nbytes = BF16 * b * s * d * (2 * h + 2 * h * kv_ratio)
    return ops, nbytes


def flash_bwd(b: int, h: int, s: int, d: int, kv_ratio: float = 1.0):
    """Causal flash backward (dq and dk/dv kernels together): the four
    matmuls the gradient needs (dV, dP, dQ, dK); the recomputed QK^T is
    recomputation and not counted. Bytes: read q, k, v, o/do, write dq,
    dk, dv."""
    ops = 4 * 2 * b * h * s * s * d / 2
    nbytes = BF16 * b * s * d * (4 * h + 4 * h * kv_ratio)
    return ops, nbytes


def bound_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of ops over peak
    FLOP/s and bytes over peak bytes/s."""
    return max(ops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
