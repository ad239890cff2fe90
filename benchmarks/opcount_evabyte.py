"""Operations and bytes of the EvaByte decoder (`models/evabyte.py` config
field names: EVA attention, a dense SwiGLU MLP a layer, `pred_heads` heads
over one byte vocabulary), computed from shapes, by `opcount.py`'s rules:
the mathematics, not what the program executes. A token is multiplied by
its layer's weights (q, k, v, o and the MLP's three) and by the whole head
(`pred_heads` x `vocab_size` columns); the embedding's lookup is no matmul;
no recomputation under remat. EVA attention at the scores the rule KEEPS, by
kind (`kept_scores`): a window's own causal bytes, and the chunk summaries
of every earlier window. The pooling of a chunk (a `d_head`-wide dot a byte
for its weight, two weighted sums) is counted too: 6 ops a channel and
byte, under 0.01% of a layer. One multiply-add = 2 ops.
"""

from __future__ import annotations

from benchmarks.opcount import BF16, bound_seconds  # noqa: F401


def kept_scores(seq: int, window: int, chunk: int):
    """-> (local, summary): the (query, key) pairs a (batch, head) keeps of
    its own window's bytes, and of the earlier windows' chunk summaries; the
    last window may be partial."""
    whole, rest = divmod(seq, window)
    local = whole * window * (window + 1) // 2 + rest * (rest + 1) // 2
    summary = sum(min(window, seq - w * window) * w * (window // chunk)
                  for w in range(whole + (rest > 0)))
    return local, summary


def layer_matmul_params(model: dict) -> int:
    d, hk = model["d_model"], model["n_heads"] * model["d_head"]
    return 4 * d * hk + 3 * d * model["d_ff"]


def layer_params(model: dict) -> int:
    """q, k, v, o; gate, up, down; two norms; phi and mu."""
    return layer_matmul_params(model) + 2 * model["d_model"] \
        + 2 * model["n_heads"] * model["d_head"]


def head_params(model: dict) -> int:
    return model["d_model"] * model["pred_heads"] * model["vocab_size"]


def num_params(model: dict) -> int:
    """What the program holds: the held layers, the embedding, the final
    norm and the untied head."""
    d = model["d_model"]
    return (model["vocab_size"] * d + model["n_layers"] * layer_params(model)
            + d + head_params(model))


def eva_flash_fwd(b: int, h: int, s: int, d: int, window: int, chunk: int):
    """The flash forward under `EvaWindows` over q [b, h, s, d] and [s /
    chunk summaries ; s bytes] keys -> (ops, bytes): QK^T and PV at the
    kept scores. Bytes: q read, o written, K and V read, bf16."""
    s_k = s + s // chunk
    ops = 2 * 2 * b * h * sum(kept_scores(s, window, chunk)) * d
    nbytes = BF16 * b * h * d * (2 * s + 2 * s_k)
    return ops, nbytes


def eva_flash_bwd(b: int, h: int, s: int, d: int, window: int, chunk: int):
    """dq and dk/dv kernels together: the four matmuls the gradient needs
    (dV, dP, dQ, dK) at the kept scores; the recomputed QK^T is not counted.
    Bytes: q, o / do, K, V read, dq, dK, dV written."""
    s_k = s + s // chunk
    ops = 4 * 2 * b * h * sum(kept_scores(s, window, chunk)) * d
    nbytes = BF16 * b * h * d * (4 * s + 4 * s_k)
    return ops, nbytes


def eva_summarise(b: int, h: int, s: int, d: int, chunk: int):
    """The pooling, forward: phi . k_j a byte, then the chunk's weighted sums
    of k and of v. Bytes: k and v read once, a chunk-th of each written:
    memory-bound."""
    ops = (2 + 2 * 2) * b * h * s * d
    nbytes = BF16 * b * h * d * (2 * s + 2 * s // chunk)
    return ops, nbytes


def forward_flops_by_part(model: dict, seq: int) -> dict:
    """Forward operations a token, by part."""
    hd = model["n_heads"] * model["d_head"]
    local, summary = kept_scores(seq, model["window"], model["chunk"])
    n = model["n_layers"]
    return {
        "projections": n * 2 * 4 * model["d_model"] * hd,
        "mlp": n * 2 * 3 * model["d_model"] * model["d_ff"],
        "scores_local": n * 2 * 2 * hd * local / seq,
        "scores_summary": n * 2 * 2 * hd * summary / seq,
        "pooling": n * 6 * hd,
        "head": 2 * head_params(model),
    }


def forward_flops_per_token(model: dict, seq: int) -> float:
    return sum(forward_flops_by_part(model, seq).values())


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward = 3 x forward."""
    return 3.0 * forward_flops_per_token(model, seq)
