"""Llama stack + routed experts: the Mixtral and OLMoE families, TPU-first.

`LlamaConfig` and llama's parameters with the dense MLP taken out
(whole-model reuse: one of the two model-to-model edges left, with
`sdar -> mixtral`); the attention sublayer is `blocks.attn_sublayer` (GQA or
MHA, RoPE, optional QK-norm); the MLP is top-k routed SwiGLU experts through
`parallel/moe.py`: one dropless sorted dispatch over grouped matmuls, or, on
a mesh with an `ep` axis, experts sharded across chips with a
capacity-bounded `all_to_all` exchange. The two families differ in static
config only:

- Mixtral: GQA, top-2 of 8, the k weights renormalised (`norm_topk_prob`).
- OLMoE: MHA, `qk_norm`, top-8 of 64, weights not renormalised, and a
  router z-loss beside the load-balancing loss (arXiv:2409.02060).

Training loss: CE + aux_loss_coef * mean_l LB_l + router_z_loss_coef *
mean_l RZ_l with the per-layer terms of `parallel/moe.router_losses`.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import blocks, experts, llama
from ray_tpu.models.blocks import remat_policy, rms_norm
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.parallel.moe import MoEAux, moe_layer, moe_shard_map
from ray_tpu.parallel.sharding import LogicalAxisRules, with_logical_constraint


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig, experts.Share):
    """`d_ff` is the width of ONE expert. `n_experts` is the router's
    outputs; with `n_experts_held` fewer than that this program is one chip's
    share of an expert-parallel deployment run without its exchange: it
    holds experts [`first_expert`, `first_expert + n_experts_held`) and
    leaves out what the absent ones would add (`moe_layer`'s `held`)."""
    n_experts: int = 8
    n_experts_held: Optional[int] = None   # None: all of them
    first_expert: int = 0
    experts_per_token: int = 2
    norm_topk_prob: bool = True
    aux_loss_coef: float = 0.01        # load balancing
    router_z_loss_coef: float = 0.0
    capacity_factor: float = 1.25      # read by the `ep` exchange only

    @staticmethod
    def tiny(vocab_size: int = 512) -> "MixtralConfig":
        return MixtralConfig(
            vocab_size=vocab_size, d_model=128, n_layers=2, n_heads=4,
            n_kv_heads=2, d_head=32, d_ff=256, max_seq_len=512,
            n_experts=4, experts_per_token=2,
        )

    def num_params(self) -> int:
        base = super().num_params()
        # replace the dense FFN count with the held routed FFNs + gate
        dense_ffn = self.n_layers * 3 * self.d_model * self.d_ff
        n_held = self.held[1] if self.held else self.n_experts
        moe_ffn = self.n_layers * (
            n_held * 3 * self.d_model * self.d_ff
            + self.d_model * self.n_experts)
        return base - dense_ffn + moe_ffn


def param_logical_axes(config: MixtralConfig) -> Dict[str, Any]:
    axes = llama.param_logical_axes(config)
    layer_axes = axes["layers"]
    for k in ("w_gate", "w_up", "w_down"):
        layer_axes.pop(k, None)
    L = ("layers",)
    # a share's experts are not the `ep` axis's: it has no exchange
    expert = "expert" if config.held is None else None
    layer_axes["moe_gate"] = L + ("embed", expert)
    layer_axes["experts"] = {
        "w_gate": L + (expert, "embed", "mlp"),
        "w_up": L + (expert, "embed", "mlp"),
        "w_down": L + (expert, "mlp", "embed"),
    }
    return axes


def init(config: MixtralConfig, key) -> Dict[str, Any]:
    c = config
    params = llama.init(c, key)
    layers = params["layers"]
    for k in ("w_gate", "w_up", "w_down"):
        layers.pop(k, None)
    k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(key, 0xE), 4)
    scale_in = (2.0 / (c.d_model + c.d_ff)) ** 0.5
    n_held = c.held[1] if c.held else c.n_experts
    # Leading axis n_layers (scanned), then the experts held (all of them:
    # sharded on `ep`).
    layers["moe_gate"] = (
        jax.random.normal(k1, (c.n_layers, c.d_model, c.n_experts)) * 0.02
    ).astype(c.dtype)
    layers["experts"] = {
        "w_gate": (jax.random.normal(
            k2, (c.n_layers, n_held, c.d_model, c.d_ff)) * scale_in
        ).astype(c.dtype),
        "w_up": (jax.random.normal(
            k3, (c.n_layers, n_held, c.d_model, c.d_ff)) * scale_in
        ).astype(c.dtype),
        "w_down": (jax.random.normal(
            k4, (c.n_layers, n_held, c.d_ff, c.d_model)) * scale_in
        ).astype(c.dtype),
    }
    return params


def _expert_ffn(p, x):
    """One expert's SwiGLU FFN. p: dict of [d,f],[d,f],[f,d]; x: [t, d]."""
    gate = x @ p["w_gate"]
    up = x @ p["w_up"]
    return (jax.nn.silu(gate) * up) @ p["w_down"]


def moe_block(h, layer_p, config: MixtralConfig, mesh):
    """h: [B,S,D] -> (out [B,S,D], MoEAux of this layer)."""
    c = config
    b, s, d = h.shape
    flat = h.reshape(b * s, d)
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        if c.held is not None:
            raise NotImplementedError(
                "a share runs without the exchange: no `ep` mesh axis")
        out, aux = moe_shard_map(
            flat, layer_p["moe_gate"], _expert_ffn, layer_p["experts"], mesh,
            k=c.experts_per_token, capacity_factor=c.capacity_factor,
            norm_topk_prob=c.norm_topk_prob)
    else:
        out, aux = moe_layer(
            flat, layer_p["moe_gate"], layer_p["experts"],
            k=c.experts_per_token, norm_topk_prob=c.norm_topk_prob,
            held=c.held)
    return out.reshape(b, s, d), aux


def hidden_states(params, tokens, config: MixtralConfig, mesh=None,
                  rules: Optional[LogicalAxisRules] = None, positions=None,
                  mask=None):
    """tokens [B,S] -> (the last layer's output [B,S,D], BEFORE the final
    norm, MoEAux whose fields lead with the layer dim: experts [L, B*S, k],
    the loss terms [L]). `positions` [B,S] (default 0..S-1) turn RoPE;
    `mask` is attention's static rule (default causal)."""
    c = config
    lc = partial(with_logical_constraint, mesh=mesh, rules=rules)
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    # the table's embed dim in the activation layout: `blocks.embed_tokens`
    table = lc(params["embed"], ("vocab", "act_embed"))
    x = blocks.embed_rows(table, tokens, mesh).astype(c.dtype)
    x = lc(x, ("batch", "seq", "act_embed"))

    def layer_fn(x, layer_p):
        x = blocks.attn_sublayer(x, layer_p, positions, c, mesh, rules, mask)
        h2 = rms_norm(x, layer_p["mlp_norm"], c.norm_eps)
        moe_out, aux = moe_block(h2, layer_p, c, mesh)
        return lc(x + moe_out, ("batch", "seq", "act_embed")), aux

    if c.remat:
        layer_fn = jax.checkpoint(layer_fn, policy=remat_policy(c))

    return jax.lax.scan(layer_fn, x, params["layers"])


def forward_hidden(params, tokens, config: MixtralConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B,S] -> (final-norm hidden states [B,S,D], MoEAux per
    layer, as `hidden_states`)."""
    x, aux = hidden_states(params, tokens, config, mesh, rules)
    return rms_norm(x, params["final_norm"], config.norm_eps), aux


def forward(params, tokens, config: MixtralConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """tokens [B,S] -> (logits [B,S,V] fp32, MoEAux per layer)."""
    x, aux = forward_hidden(params, tokens, config, mesh, rules)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits.astype(jnp.float32), aux


def aux_loss(aux: MoEAux, config: MixtralConfig):
    """The router's part of the training loss, float32 scalar."""
    return (config.aux_loss_coef * jnp.mean(aux.load_balance)
            + config.router_z_loss_coef * jnp.mean(aux.router_z))


def loss_fn(params, batch, config: MixtralConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """Next-token CE (masked by batch["mask"] when given; in sequence chunks
    of `loss_chunk_size`, or one chunk, through `blocks.chunked_ce`) +
    `aux_loss`, whose terms are statistics of EVERY token of the batch,
    masked or not.
    Scalar return (make_train_step contract, train/step.py:100)."""
    inputs, targets, mask = blocks.split_batch(batch)
    hidden, aux = forward_hidden(params, inputs, config, mesh, rules)
    ce = blocks.chunked_ce(hidden, params["lm_head"], targets, mask,
                           chunk=config.loss_chunk_size or hidden.shape[1])
    return ce + aux_loss(aux, config)
