"""Operations and bytes of the Nemotron-H decoder (`models/nemotron_h.py`
config field names: Mamba-2 layers, experts in a latent, GQA attention, one
sublayer a layer, an MTP block), computed from shapes, by `opcount.py`'s
rules: the mathematics, not what the program executes. A token is multiplied
by its layer's weights: a Mamba-2 layer's two projections and its conv's
taps; an attention layer's four; an expert layer's router, latent
projections, shared expert and the routed experts it is sent to THAT ARE
HELD HERE (in expectation k x held / all); the MTP block's and the lm_head
twice (the MTP block predicts through it too); no embedding gather, no
recomputation under remat, no backward pass through a share's router
(`moe_layer`: a share's combine weights are constants). Causal attention at
its causal half. The state-space scan is counted in its CHUNKED form
(`ops/ssd.py`, chunk 128): that is the algorithm whose matmuls run; the
token-by-token recurrence would be 6 P N ops a token and head, 0.47x of
it. One multiply-add = 2 ops.
"""

from __future__ import annotations

from benchmarks.opcount import (  # noqa: F401
    BF16,
    bound_seconds,
    flash_bwd,
    flash_fwd,
)

F32 = 4
CHUNK = 128
KINDS = ("M", "E", "*")


def _layers(model: dict) -> list:
    held = model.get("layers")
    return list(range(len(model["pattern"]))) if held is None else list(held)


def _widths(model: dict):
    """-> (the Mamba heads' channels H x P, the conv's channels)."""
    wide = model["mamba_heads"] * model["mamba_head_dim"]
    return wide, wide + 2 * model["n_groups"] * model["state_size"]


def layer_params(model: dict, kind: str) -> int:
    """One layer's parameters, its norm's d_model in."""
    d = model["d_model"]
    if kind == "M":
        wide, conv = _widths(model)
        h = model["mamba_heads"]
        return (d * (wide + conv + h) + (model.get("conv_size", 4) + 1) * conv
                + 3 * h + wide + wide * d + d)
    if kind == "*":
        return 2 * d * model["d_head"] * (
            model["n_heads"] + model["n_kv_heads"]) + d
    return (d * model["n_experts"] + model["n_experts"]
            + 2 * d * model["latent_size"] + 2 * d * model["d_ff_shared"]
            + model["n_experts_held"] * 2 * model["latent_size"]
            * model["d_ff_expert"] + d)


def _mtp_pattern(model: dict) -> str:
    return model.get("mtp_pattern", "*E") if model.get("mtp_depth", 1) else ""


def num_params(model: dict) -> int:
    """What the program holds: the held layers, embedding, head, final
    norm, the MTP block (W_eh, three norms, its layers)."""
    d = model["d_model"]
    mtp = _mtp_pattern(model)
    return (2 * model["vocab_size"] * d + d
            + sum(layer_params(model, model["pattern"][i])
                  for i in _layers(model))
            + (2 * d * d + 3 * d + sum(layer_params(model, k) for k in mtp)
               if mtp else 0))


def ssd_chunk_ops(heads: int, p: int, n_state: int, chunk: int = CHUNK):
    """The chunked form's matmuls for one chunk of one GROUP of `heads`
    heads, forward: C B^T once, and a head: ((C B^T) * L) (Delta x), the
    state's read C H^T and its update B^T (w Delta x)."""
    c = chunk
    return 2 * c * c * n_state + heads * (2 * c * c * p + 2 * 2 * c * n_state * p)


def _ssd_scalars(b, h, s):
    """Delta and the log decay, [b, s, h] float32 each."""
    return 2 * F32 * b * s * h


def ssd_fwd(b: int, h: int, s: int, p: int, groups: int, n_state: int):
    """`ops/ssd.py` forward over x [b, s, h, p], B and C [b, s, groups,
    n_state] -> (ops, bytes). Bytes, each operand once: x read and y
    written in bf16, B and C read in bf16, Delta and the log decay read in
    float32, the final state written."""
    chunks = b * groups * -(-s // CHUNK)
    nbytes = (BF16 * b * s * (2 * h * p + 2 * groups * n_state)
              + _ssd_scalars(b, h, s) + F32 * b * h * p * n_state)
    return chunks * ssd_chunk_ops(h // groups, p, n_state), nbytes


def ssd_bwd(b: int, h: int, s: int, p: int, groups: int, n_state: int):
    """The backward pass: two matmuls for each of the forward's; the first
    walk's recomputed states are recomputation and not counted. Bytes: x,
    B, C, Delta, the log decay and dy read, dx, dB, dC (bf16), dDelta and
    the decay's gradient (float32) written."""
    chunks = b * groups * -(-s // CHUNK)
    nbytes = (BF16 * b * s * (3 * h * p + 4 * groups * n_state)
              + 2 * _ssd_scalars(b, h, s))
    return 2 * chunks * ssd_chunk_ops(h // groups, p, n_state), nbytes


def _layer_token_ops(model: dict, kind: str, seq: int) -> float:
    """Forward ops a token of one layer."""
    d = model["d_model"]
    if kind == "M":
        wide, conv = _widths(model)
        h, g = model["mamba_heads"], model["n_groups"]
        scan = g * ssd_chunk_ops(h // g, model["mamba_head_dim"],
                                 model["state_size"]) / CHUNK
        return 2 * (d * (wide + conv + h) + wide * d
                    + model.get("conv_size", 4) * conv) + scan
    if kind == "*":
        weights = 2 * d * model["d_head"] * (
            model["n_heads"] + model["n_kv_heads"])
        return 2 * weights + 2 * model["n_heads"] * model["d_head"] * seq
    held_pairs = (model["experts_per_token"] * model["n_experts_held"]
                  / model["n_experts"])
    return 2 * (d * model["n_experts"] + 2 * d * model["latent_size"]
                + 2 * d * model["d_ff_shared"]
                + held_pairs * 2 * model["latent_size"] * model["d_ff_expert"])


def frozen_router_params(model: dict) -> int:
    """As `opcount_joyai.frozen_router_params`: the routers of a share run
    forward and get no gradient."""
    if model["n_experts_held"] == model["n_experts"]:
        return 0
    routed = sum(model["pattern"][i] == "E" for i in _layers(model)) \
        + _mtp_pattern(model).count("E")
    return routed * model["d_model"] * model["n_experts"]


def head_token_ops(model: dict) -> float:
    return 2.0 * model["d_model"] * model["vocab_size"]


def forward_flops_per_token(model: dict, seq: int) -> float:
    d = model["d_model"]
    mtp = _mtp_pattern(model)
    total = head_token_ops(model) + sum(
        _layer_token_ops(model, model["pattern"][i], seq)
        for i in _layers(model))
    if mtp:
        total += 2 * 2 * d * d + head_token_ops(model) + sum(
            _layer_token_ops(model, k, seq) for k in mtp)
    return total


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward = 3 x forward, less the backward (2 x forward) of
    a share's frozen routers."""
    return 3.0 * forward_flops_per_token(model, seq) \
        - 2.0 * 2 * frozen_router_params(model)
