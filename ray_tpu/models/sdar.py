"""Block-diffusion training of a routed-experts decoder: the SDAR family
(SDAR-30B-A3B-Chat by config: Qwen3-MoE layers), TPU-first.

The layers are `models/mixtral.py`'s, through `mixtral.hidden_states`
(whole-model reuse: one of the two model-to-model edges left, with
`mixtral -> llama`): `blocks.attn_sublayer` + top-k routed SwiGLU experts
through `parallel/moe.py`, all experts or one chip's share,
`n_experts_held`, with Qwen3's per-head QK-norm: an RMSNorm of every head
over its own `d_head` channels, one `[d_head]` scale for all q heads and one
for all kv heads (`blocks.qk_norm` reads the form off the scale's shape).
What differs is the training step, BD3-LM's vectorised block-diffusion
objective (arXiv:2503.09573), which SDAR's modelling code follows:

- x_0 [B, L] is the data. Per row t ~ U(0, 1), p = (1 - eps) t + eps; each
  token is replaced by `mask_token_id` with probability p, independently
  -> x_t (`noise`: a pure function of the row's ids and `noise_seed`, so a
  repeated batch repeats its mask).
- ONE forward pass over [x_t ; x_0] [B, 2L] with positions [0..L-1 ; 0..L-1]
  under `ops/flash_attention.BlockDiffusion(L, block)`: block-diagonal
  inside each half, block-causal inside x_0, offset block-causal from x_t
  to x_0. Twice the rows of a causal step, a mask that is neither causal
  nor full, repeated positions.
- loss = sum over the NOISED positions i of the x_t half of
  -log softmax(W_head RMSNorm(y_i))[x_0,i] / p_row, over the number of data
  tokens (`batch["mask"]`'s sum), no shift: position i predicts token i;
  + `aux_loss_coef` * mean_l LB_l over all 2L rows. The final norm and the
  head run on the x_t half only.

Generation by diffusion over blocks (several tokens a row a step, at
serving) is NOT here: this module trains.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler
from ray_tpu.models import blocks, experts, mixtral
from ray_tpu.models.mixtral import MixtralConfig
from ray_tpu.ops.flash_attention import BlockDiffusion
from ray_tpu.parallel.sharding import LogicalAxisRules


@dataclasses.dataclass(frozen=True)
class SdarConfig(MixtralConfig):
    """`MixtralConfig` (`d_ff` ONE expert's width, `n_experts` the router's
    outputs, `n_experts_held` / `first_expert` the share) + the objective."""
    qk_norm: bool = True
    norm_topk_prob: bool = True
    aux_loss_coef: float = 0.001
    block: int = 4                  # tokens a diffusion block
    noise_eps: float = 1e-3
    noise_seed: int = 0
    mask_token_id: Optional[int] = None   # None: the vocabulary's last row

    @property
    def mask_id(self) -> int:
        return self.vocab_size - 1 if self.mask_token_id is None \
            else self.mask_token_id

    @staticmethod
    def tiny(vocab_size: int = 512, **over) -> "SdarConfig":
        return SdarConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, d_head=16, d_ff=32, n_experts=8,
            experts_per_token=2, max_seq_len=128), **over})

    def num_params(self) -> int:
        # per-head scales [d_head] where the parent counts [H, d_head]
        return super().num_params() - self.n_layers * (
            self.n_heads + self.n_kv_heads - 2) * self.d_head


def param_logical_axes(config: SdarConfig) -> Dict[str, Any]:
    axes = mixtral.param_logical_axes(config)
    axes["layers"]["q_norm"] = axes["layers"]["k_norm"] = ("layers", "kv")
    return axes


def init(config: SdarConfig, key) -> Dict[str, Any]:
    """`mixtral.init`, with the QK-norm scales per head ([d_head]) and the
    embedding's rows seeded by what they stand for:

    - a DATA token's row is N(0, 1), of unit RMS like every sublayer's
      normed input and not fan-in scaled (a lookup sums over nothing): rows
      that stand out of the residual stream keep the tokens apart at random
      weights, so the experts' loads are near even at every seed, as a
      deployment's balancing keeps them (`mla_moe.init`, PERF.md section 6,
      PR 32);
    - the MASK token's row has RMS d_model ** -0.5. Half the x_t rows of a
      step, a quarter of all it processes, are that one token. With a row
      of unit RMS their residual stream would be that one vector in every
      layer: all of them would rank the experts alike, so how many of their
      top-k a share holds would be the seed's luck, layer by layer (a swing
      of ~4,096 live rows a held favourite), and a near-tie between the
      k-th and the next expert would flip for all of them at once against
      the float32 reference. A small row leaves their stream to what
      attention brings them, which depends on the position and its context,
      as a trained model's masked positions do."""
    c = config
    params = mixtral.init(c, key)
    layers = params["layers"]
    layers["q_norm"] = jnp.ones((c.n_layers, c.d_head), dtype=c.dtype)
    layers["k_norm"] = jnp.ones((c.n_layers, c.d_head), dtype=c.dtype)
    rows = jax.random.normal(jax.random.fold_in(key, 0x5DA),
                             (c.vocab_size, c.d_model), dtype=jnp.float32)
    is_mask = (jnp.arange(c.vocab_size) == c.mask_id)[:, None]
    params["embed"] = jnp.where(
        is_mask, rows * c.d_model ** -0.5, rows).astype(c.dtype)
    return params


# --------------------------------------------------------------------------
# the objective
# --------------------------------------------------------------------------

def _mix(x):
    """A 32-bit integer hash (lowbias32) of uint32 arrays, elementwise."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _unit(h):
    """uint32 -> float32 in [0, 1): the top 24 bits, exact."""
    return (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def noise(x_0, config: SdarConfig):
    """x_0 [B, L] -> (noised [B, L] bool, p [B] float32): each row's masking
    probability p = (1 - eps) t + eps with its own t, and which of its
    tokens are masked (each with probability p, independently). A pure
    function of the row's ids and `noise_seed` (integer hashes, no key to
    carry): the harness hands `loss_fn` a batch and nothing else, and a
    repeated batch must repeat its mask or a falling loss means nothing.
    `benchmarks/reference_sdar.py` has its own lines for the same rule."""
    c = config
    length = x_0.shape[1]
    at = jnp.arange(1, length + 1, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9)
    row = _mix(jnp.uint32(c.noise_seed) + jnp.sum(
        _mix(x_0.astype(jnp.uint32) + at[None]), axis=1, dtype=jnp.uint32))
    t = _unit(_mix(row ^ jnp.uint32(0xB5297A4D)))
    p = jnp.float32(1.0 - c.noise_eps) * t + jnp.float32(c.noise_eps)
    return _unit(_mix(row[:, None] + at[None])) < p[:, None], p


def hidden_states(params, x_t, x_0, config: SdarConfig, mesh=None,
                  rules: Optional[LogicalAxisRules] = None):
    """x_t, x_0 [B, L] -> (the last layer's output over [x_t ; x_0]
    [B, 2L, D], before the final norm, MoEAux per layer over all 2L rows).
    Both halves carry positions 0..L-1; attention runs under
    `BlockDiffusion(L, block)`, so the x_0 half never sees the x_t half."""
    c = config
    b, length = x_0.shape
    positions = jnp.broadcast_to(
        jnp.tile(jnp.arange(length), 2), (b, 2 * length))
    # per LOWERING, as `flash.steps_*` are
    device_profiler.count("bd.block", c.block)
    device_profiler.count("bd.rows_noised", b * length)
    device_profiler.count("bd.rows_clean", b * length)
    return mixtral.hidden_states(
        params, jnp.concatenate([x_t, x_0], axis=1), c, mesh, rules,
        positions=positions, mask=BlockDiffusion(length, c.block))


def forward_hidden(params, x_t, x_0, config: SdarConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """-> (final-norm hidden states of the x_t half [B, L, D], MoEAux per
    layer): the head never sees the x_0 half."""
    x, aux = hidden_states(params, x_t, x_0, config, mesh, rules)
    return blocks.rms_norm(x[:, :x_0.shape[1]], params["final_norm"],
                           config.norm_eps), aux


def _data(batch):
    if "inputs" in batch:
        return batch["inputs"], batch.get("mask")
    return batch["tokens"][:, :-1], None


def noised_batch(batch, x_0, config: SdarConfig):
    """(noised, p, x_t): the batch's own draw (`noise_mask` [B, L] bool,
    `noise_p` [B]) where a data pipeline made one, else `noise`'s."""
    with jax.named_scope("bd.noise"):
        if "noise_mask" in batch:
            noised = batch["noise_mask"].astype(bool)
            p = batch["noise_p"].astype(jnp.float32)
        else:
            noised, p = noise(x_0, config)
        x_t = jnp.where(noised, jnp.asarray(config.mask_id, x_0.dtype), x_0)
    return noised, p, x_t


def loss_fn(params, batch, config: SdarConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """The block-diffusion loss of the module's docstring over
    batch["inputs"] (batch["targets"], the next tokens, are not read:
    position i predicts token i) + `mixtral.aux_loss`. batch["mask"]
    selects the data tokens that count. Scalar return (make_train_step
    contract)."""
    c = config
    x_0, mask = _data(batch)
    noised, p, x_t = noised_batch(batch, x_0, c)
    hidden, aux = forward_hidden(params, x_t, x_0, c, mesh, rules)
    with jax.named_scope("bd.loss"):
        weights = noised.astype(jnp.float32) / p[:, None]
        if mask is not None:
            weights = weights * mask
        data_tokens = jnp.float32(x_0.size) if mask is None \
            else jnp.maximum(jnp.sum(mask), 1.0)
        ce = blocks.chunked_ce(
            hidden, params["lm_head"], x_0, weights,
            chunk=c.loss_chunk_size or hidden.shape[1],
            denominator=data_tokens)
    return ce + mixtral.aux_loss(aux, c)


@partial(jax.jit, static_argnames=("config",))
def routing_stats(params, tokens, config: SdarConfig):
    """tokens [B, L + 1] (as `loss_fn`'s {"tokens": ...}) -> int32
    [n_layers]: the LIVE rows of each layer, the (row, choice) pairs of all
    2L rows a sequence whose expert is held here. Outside the train step,
    for tests and chip runs."""
    c = config
    x_0, _ = _data({"tokens": tokens})
    _, _, x_t = noised_batch({}, x_0, c)
    _, aux = hidden_states(params, x_t, x_0, c)
    return experts.live_rows(aux.experts, c)


def routing_loads(params, tokens, config: SdarConfig):
    """-> float32 [n_layers]: each layer's live rows over the rows of the
    capacity it runs at (`experts.capacity_loads`)."""
    return experts.capacity_loads(
        routing_stats(params, tokens, config),
        2 * tokens.shape[0] * (tokens.shape[1] - 1), config)
