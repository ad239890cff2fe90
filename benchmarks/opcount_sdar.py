"""Operations and bytes of block-diffusion training over routed-experts
layers (`models/sdar.py` config field names), computed from shapes, by
`opcount.py`'s rules: the mathematics, not what the program executes.

A DATA token is what `train_tokens_per_s_per_chip` counts (the traffic's
batch x seq), and the objective processes it twice: a noised copy and a
clean copy go through every layer (2 rows a data token), the final norm and
the lm_head see the noised copy only (1 row). A row is multiplied by the
attention projections, the router, and the experts it is sent to THAT ARE
HELD HERE (in expectation k x held / all: which pairs land on a share is
data, and no reader sees a step's live rows); attention at the scores the
mask KEEPS, length^2 + length x block a (batch, head) over the 2 x length
rows, not at a causal half. No embedding gather, no recomputation under
remat. A share's router still learns (from the load-balancing loss), so its
backward pass is counted. One multiply-add = 2 ops.
"""

from __future__ import annotations

from benchmarks.opcount import BF16, bound_seconds  # noqa: F401


def layer_params(model: dict) -> int:
    """Parameters of one layer held here: q, k, v, o, the router, the held
    experts, the two layer norms and the two [d_head] QK-norm scales."""
    d, h, kv, dh = (model[k] for k in
                    ("d_model", "n_heads", "n_kv_heads", "d_head"))
    held = model.get("n_experts_held") or model["n_experts"]
    return (2 * d * h * dh + 2 * d * kv * dh + d * model["n_experts"]
            + held * 3 * d * model["d_ff"] + 2 * d + 2 * dh)


def num_params(model: dict) -> int:
    """Layers + embedding + head + final norm."""
    d = model["d_model"]
    return (model["n_layers"] * layer_params(model)
            + 2 * model["vocab_size"] * d + d)


def row_active_matmul_params(model: dict) -> float:
    """Weights ONE processed row of one layer is multiplied by: the
    attention projections, the router, and its expected held pairs,
    experts_per_token x n_experts_held / n_experts experts."""
    d, h, kv, dh = (model[k] for k in
                    ("d_model", "n_heads", "n_kv_heads", "d_head"))
    held = model.get("n_experts_held") or model["n_experts"]
    pairs = model["experts_per_token"] * held / model["n_experts"]
    return (2 * d * h * dh + 2 * d * kv * dh + d * model["n_experts"]
            + pairs * 3 * d * model["d_ff"])


def kept_scores(length: int, block: int) -> int:
    """Scores the block-diffusion mask keeps a (batch, head) over the
    2 x length concatenation: (L^2 + L block) / 2 in the block-causal x_0
    half and as many for the x_t rows (their own block + the earlier clean
    ones)."""
    return length * length + length * block


def forward_flops_per_token(model: dict, seq: int) -> float:
    """Per DATA token: two rows through every layer's matmuls, its share of
    the kept scores (QK^T and PV over d_head, all heads), one row through
    the head."""
    attn = 2 * 2 * model["n_heads"] * model["d_head"] \
        * kept_scores(seq, model["block"]) / seq
    return (model["n_layers"]
            * (2 * 2 * row_active_matmul_params(model) + attn)
            + 2 * model["d_model"] * model["vocab_size"])


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, `seq` the DATA tokens a row."""
    return 3.0 * forward_flops_per_token(model, seq)


def bd_flash_fwd(b: int, h: int, s: int, d: int, block: int,
                 kv_ratio: float = 1.0):
    """Flash forward under the block-diffusion mask over [b, h, s, d],
    s = 2 x length -> (ops, bytes). Ops: QK^T and PV at the KEPT scores.
    Bytes: read q, k, v (k, v at the model's kv heads), write o, all bf16."""
    ops = 2 * 2 * b * h * kept_scores(s // 2, block) * d
    return ops, BF16 * b * s * d * (2 * h + 2 * h * kv_ratio)


def bd_flash_bwd(b: int, h: int, s: int, d: int, block: int,
                 kv_ratio: float = 1.0):
    """The backward pass (dq and dk/dv kernels together): the four matmuls
    the gradient needs (dV, dP, dQ, dK) at the kept scores; the recomputed
    QK^T is recomputation and not counted. Bytes: read q, k, v, o/do, write
    dq, dk, dv."""
    ops = 4 * 2 * b * h * kept_scores(s // 2, block) * d
    return ops, BF16 * b * s * d * (4 * h + 4 * h * kv_ratio)
