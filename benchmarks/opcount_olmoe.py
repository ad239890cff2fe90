"""Operations and bytes of the routed-experts models (`models/mixtral.py`
config field names), computed from shapes, by `opcount.py`'s rules: the
mathematics, not what the program executes. A token is multiplied by its
k ACTIVE experts of E, by the router, the attention projections and the
lm_head; no embedding gather, no recomputation under remat, causal
attention at its causal half. One multiply-add = 2 ops.
"""

from __future__ import annotations

from benchmarks.opcount import (  # noqa: F401  (the readers' contract)
    BF16,
    bound_seconds,
    flash_bwd,
    flash_fwd,
)


def layer_active_matmul_params(model: dict) -> int:
    d, h, kv, dh = (model[k] for k in
                    ("d_model", "n_heads", "n_kv_heads", "d_head"))
    attention = d * h * dh + 2 * d * kv * dh + h * dh * d
    router = d * model["n_experts"]
    experts = model["experts_per_token"] * 3 * d * model["d_ff"]
    return attention + router + experts


def active_matmul_params(model: dict) -> int:
    """Weights one token is multiplied by: its share of the layers and the
    lm_head. The embedding table is gathered from, not multiplied."""
    return (model["n_layers"] * layer_active_matmul_params(model)
            + model["d_model"] * model["vocab_size"])


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward = 3 x forward. Forward: 2 ops per active weight,
    plus causal attention's two matmuls over on average seq/2 keys."""
    attn = 2 * model["n_heads"] * model["d_head"] * seq
    return 3.0 * (2 * active_matmul_params(model) + model["n_layers"] * attn)


def moe_gmm(m: int, k: int, n: int, groups: int):
    """One grouped matmul [m, k] x [groups, k, n] -> [m, n] -> (ops, bytes).
    Ops: every row times one [k, n] matrix. Bytes: read the rows and every
    group's matrix, write the result, all bf16. The backward's two calls
    (rows' gradient: [m, n] x [groups, n, k]; weights' gradient: [k, m] x
    [m, n] per group) have the same ops and the same three operands."""
    return 2 * m * k * n, BF16 * (m * k + groups * k * n + m * n)
