"""Operations and bytes of the Granite-4.0-H decoder (`models/
granite_hybrid.py` config field names: every layer a mixer, Mamba-2 or GQA
attention, AND a dense gated MLP; a tied head), computed from shapes, by
`opcount.py`'s rules: the mathematics, not what the program executes. A
token is multiplied by its layer's weights (a Mamba-2 mixer's two
projections and its conv's taps, or an attention mixer's four, and the
MLP's three) and by the head, which is the embedding, ONCE (the lookup is no
matmul); no recomputation under remat. Causal attention at the scores it
KEEPS (s (s + 1) / 2 a head), a score a 64-wide contraction: a kernel that
padded the head to 128 lanes would read at most half of such a bound. The
state-space scan is counted in its CHUNKED form at the chunk the program
walks (`ops/ssd.py`, `CHUNK` below = the configuration's `chunk_size`),
C B^T once a GROUP: walked in head blocks the kernels form it once a block.
One multiply-add = 2 ops.
"""

from __future__ import annotations

from benchmarks import opcount_nemotron3
from benchmarks.opcount import BF16, bound_seconds  # noqa: F401
from benchmarks.opcount_nemotron3 import ssd_chunk_ops

# the configuration's `chunk_size`: `ssd_readers.kernel_roofline` reads it
CHUNK = 256
KINDS = ("mamba", "attention")


def _layers(model: dict) -> list:
    held = model.get("layers")
    return list(range(len(model["pattern"]))) if held is None else list(held)


def _widths(model: dict):
    """-> (the Mamba heads' channels H x P, the conv's channels)."""
    wide = model["mamba_heads"] * model["mamba_head_dim"]
    return wide, wide + 2 * model["n_groups"] * model["state_size"]


def mixer_params(model: dict, kind: str) -> int:
    d = model["d_model"]
    if kind == "mamba":
        wide, conv = _widths(model)
        h = model["mamba_heads"]
        return (d * (wide + conv + h) + (model.get("conv_size", 4) + 1) * conv
                + 3 * h + wide + wide * d)
    return 2 * d * model["d_head"] * (model["n_heads"] + model["n_kv_heads"])


def layer_params(model: dict, kind: str) -> int:
    """One layer's parameters: its mixer, its MLP, its two norms."""
    d = model["d_model"]
    return mixer_params(model, kind) + 3 * d * model["d_ff"] + 2 * d


def num_params(model: dict) -> int:
    """What the program holds: the held layers, the embedding (which is the
    head) and the final norm."""
    d = model["d_model"]
    return model["vocab_size"] * d + d + sum(
        layer_params(model, model["pattern"][i]) for i in _layers(model))


def _scan_ops(b, h, s, p, groups, n_state):
    """The chunked form's forward matmuls of a call at this chunk."""
    return b * groups * -(-s // CHUNK) * ssd_chunk_ops(
        h // groups, p, n_state, CHUNK)


def ssd_fwd(b: int, h: int, s: int, p: int, groups: int, n_state: int):
    """`opcount_nemotron3.ssd_fwd`'s bytes (each operand once: no chunk in
    them), the ops at this configuration's chunk."""
    nbytes = opcount_nemotron3.ssd_fwd(b, h, s, p, groups, n_state)[1]
    return _scan_ops(b, h, s, p, groups, n_state), nbytes


def ssd_bwd(b: int, h: int, s: int, p: int, groups: int, n_state: int):
    """`opcount_nemotron3.ssd_bwd`'s bytes; two matmuls for each of the
    forward's at this configuration's chunk."""
    nbytes = opcount_nemotron3.ssd_bwd(b, h, s, p, groups, n_state)[1]
    return 2 * _scan_ops(b, h, s, p, groups, n_state), nbytes


def kept_scores(s: int) -> int:
    """The (query, key) pairs a causal head keeps."""
    return s * (s + 1) // 2


def flash_fwd(b: int, h: int, s: int, d: int, kv_ratio: float = 1.0):
    """Causal flash forward over [b, h, s, d] -> (ops, bytes): QK^T and PV
    over the kept scores, a d-wide contraction each. Bytes: q read, o
    written, k and v read at the model's KV heads, bf16."""
    ops = 2 * 2 * b * h * kept_scores(s) * d
    nbytes = BF16 * b * s * d * (2 * h + 2 * h * kv_ratio)
    return ops, nbytes


def flash_bwd(b: int, h: int, s: int, d: int, kv_ratio: float = 1.0):
    """The backward pass (dq and dk/dv kernels together): the four matmuls
    the gradient needs (dV, dP, dQ, dK); the recomputed QK^T is not counted.
    Bytes: q, k, v, o / do read, dq, dk, dv written."""
    ops = 4 * 2 * b * h * kept_scores(s) * d
    nbytes = BF16 * b * s * d * (4 * h + 4 * h * kv_ratio)
    return ops, nbytes


def scan_token_ops(model: dict) -> float:
    """A Mamba-2 layer's chunked scan, forward ops a token."""
    h, g = model["mamba_heads"], model["n_groups"]
    chunk = model.get("chunk_size", CHUNK)
    return g * ssd_chunk_ops(h // g, model["mamba_head_dim"],
                             model["state_size"], chunk) / chunk


def mixer_token_ops(model: dict, kind: str, seq: int) -> float:
    """Forward ops a token of one layer's mixer."""
    d = model["d_model"]
    if kind == "mamba":
        wide, conv = _widths(model)
        return 2 * (d * (wide + conv + model["mamba_heads"]) + wide * d
                    + model.get("conv_size", 4) * conv) + scan_token_ops(model)
    scores = 2 * 2 * model["n_heads"] * model["d_head"] \
        * kept_scores(seq) / seq
    return 2 * mixer_params(model, kind) + scores


def mlp_token_ops(model: dict) -> float:
    return 2.0 * 3 * model["d_model"] * model["d_ff"]


def head_token_ops(model: dict) -> float:
    return 2.0 * model["d_model"] * model["vocab_size"]


def forward_flops_per_token(model: dict, seq: int) -> float:
    return head_token_ops(model) + sum(
        mixer_token_ops(model, model["pattern"][i], seq)
        + mlp_token_ops(model) for i in _layers(model))


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward + backward = 3 x forward."""
    return 3.0 * forward_flops_per_token(model, seq)
