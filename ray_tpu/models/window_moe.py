"""A decoder whose ATTENTION differs by layer: sliding-window layers among
full (global) ones in a published pattern, each kind with its own number of
query heads and its own rotary form (or none), over routed experts, beside
a shared one or not, whose router reads the feed-forward's input or, a
sublayer EARLIER, the attention's (Laguna-XS.2 and SmallThinker-21BA3B by
config: `model_type` `laguna`, `model_name` `smallthinker_21b_instruct`),
TPU-first, training only.

All pre-norm, over the layer library: `models/blocks.py` (RMSNorm, the
attention and SwiGLU sublayers, remat, the loss), `models/experts.py` (the
routed block) and `models/layer_pattern.py` (the plan and its walk). By the
three per-layer lists of the config, layer i (published index), with
h = RMSNorm(x):

- *attention*, `layer_types[i]`: GQA over `heads_per_layer[i]` query heads
  (so `wq` and `wo` differ in SHAPE by kind) and `n_kv_heads` KV heads of
  `d_head` channels, scores scaled by d_head ** -0.5.
  `sliding_attention`: query t sees keys t - `window` < j <= t
  (`ops/flash_attention.SlidingWindow`: the kernels walk the tiles the
  window touches and no others); `full_attention`: causal. RoPE in the
  form `rope_parameters` gives the kind (`blocks.Rotary`): its own theta, on
  the leading `partial_rotary_factor` of a head, under YaRN (blended
  frequencies, cos and sin times `attention_factor`) where `rope_type`
  says so; NO rotary embedding on q or k where `rope_layout[i]` is 0 (a
  list of its own: the program reads each list for what it says, and
  stacks a kind's layers, so a kind is all one or the other).
  With `attn_gate`, attn_head * sigmoid(w_head . h) before W_o;
  with `qk_norm`, an RMSNorm of every head of q and k over its own
  channels before RoPE (`blocks.qk_norm`'s [D] form).
- *feed-forward*, `mlp_layer_types[i]`: `dense`, a SwiGLU of `d_ff`;
  `sparse`, `experts.expert_sublayer` without a router bias: `score`
  ("sigmoid" or "softmax") over `n_experts`, the top `experts_per_token`
  on the scores, weights the chosen scores (over their sum with
  `norm_topk_prob`) x `routed_scaling_factor`, experts SwiGLU of
  `d_ff_expert` (`expert_form` "reglu": relu in silu's place), plus one
  shared SwiGLU of `d_ff_shared` every token passes, added unweighted
  (`d_ff_shared` 0: no shared expert, no such parameters). What the router
  reads is `router_input`: "ffn_input", the sublayer's own normed input
  RMSNorm(x + attention), as every other model here; "attention_input",
  h = RMSNorm(x) that the ATTENTION reads, so the choice of experts is
  formed before the attention call (`experts.routing`, then
  `expert_sublayer(ahead=)`); "residual", x itself before that norm. No
  auxiliary loss.

`layers` lists the published indices this program holds, in order (all by
default: the whole model). The pattern's period is the distance from one full
layer to the next, and a period here ENDS with its full layer (sliding, ...,
sliding, full): `layer_pattern.walk` scans the sparse layers that fill whole
such periods, a period its stacked sliding layers and then its full layer,
and unrolls the others (the leading dense layers; published 37-39). Remat is
per layer, the flash call's `o` and `lse` saved.

The share: `experts.py`'s (`n_experts_held`, `first_expert`).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler
from ray_tpu.models import blocks, experts, layer_pattern
from ray_tpu.models.blocks import Rotary, residual, rms_norm
from ray_tpu.ops.flash_attention import SlidingWindow
from ray_tpu.parallel.sharding import LogicalAxisRules

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
_SHORT = {FULL: "full", SLIDING: "sliding"}
# what a sparse layer's router reads (`WindowMoeConfig.router_input`)
FFN_INPUT, ATTENTION_INPUT, RESIDUAL = "ffn_input", "attention_input", \
    "residual"

PUBLISHED_ROPE = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 4096, "beta_slow": 1,
           "beta_fast": 64, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
}


def _frozen(v):
    """A JSON value as a hashable one: a config is a static argument."""
    if isinstance(v, dict):
        return tuple(sorted((k, _frozen(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(map(_frozen, v))
    return v


@dataclasses.dataclass(frozen=True)
class WindowMoeConfig(experts.Share):
    """`layer_types`, `heads_per_layer`, `mlp_layer_types`: a layer's
    attention kind (or the 0 / 1 of a published `sliding_window_layout`: 1
    a sliding layer), its query heads and its feed-forward kind by its
    published index; `layers`: the published indices held here (None: all).
    `rope_parameters`: the published group, {kind: its rotary form};
    `rope_layout`: 1 where a layer has a rotary embedding, 0 where none
    (None: every layer has)."""
    vocab_size: int = 100_352
    d_model: int = 2048
    layer_types: Tuple[str, ...] = tuple(
        FULL if i % 4 == 0 else SLIDING for i in range(40))
    heads_per_layer: Tuple[int, ...] = tuple(
        48 if i % 4 == 0 else 64 for i in range(40))
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 39
    layers: Optional[Tuple[int, ...]] = None
    n_kv_heads: int = 8
    d_head: int = 128
    window: int = 512
    rope_parameters: Any = _frozen(PUBLISHED_ROPE)
    rope_layout: Optional[Tuple[int, ...]] = None
    attn_gate: bool = True
    qk_norm: bool = False
    d_ff: int = 8192
    d_ff_expert: int = 512
    d_ff_shared: int = 512
    n_experts: int = 256           # the router's outputs
    n_experts_held: int = 256      # of them, the experts this program holds
    first_expert: int = 0
    experts_per_token: int = 8
    score: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    router_input: str = FFN_INPUT
    expert_form: str = "swiglu"
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "dots"
    loss_chunk_size: int = 0
    # what the shared sublayers also read of their config: constants here
    n_group = 1
    topk_group = 1
    use_ring_attention = False

    def __post_init__(self):
        for name in ("layer_types", "heads_per_layer", "mlp_layer_types",
                     "layers", "rope_parameters", "rope_layout"):
            v = getattr(self, name)
            if v is not None:
                object.__setattr__(self, name, _frozen(v))
        object.__setattr__(self, "layer_types", tuple(
            {0: FULL, 1: SLIDING}.get(t, t) for t in self.layer_types))
        n = len(self.layer_types)
        if not (len(self.heads_per_layer) == len(self.mlp_layer_types) == n
                == len(self.rope_layout or self.layer_types)):
            raise ValueError("the per-layer lists differ in length")
        if self.router_input not in (FFN_INPUT, ATTENTION_INPUT, RESIDUAL):
            raise ValueError(f"router_input {self.router_input!r}")
        if self.expert_form not in ("swiglu", "reglu"):
            raise ValueError(f"expert_form {self.expert_form!r}")
        if self.expert_form != "swiglu" and self.d_ff_shared:
            raise NotImplementedError(
                "a shared expert beside experts of another form than "
                "SwiGLU: the shared expert is a SwiGLU")
        if set(self.layer_types) - {FULL, SLIDING} \
                or set(self.mlp_layer_types) - {DENSE, SPARSE}:
            raise ValueError("a layer's attention is full or sliding, its "
                             "feed-forward dense or sparse")
        self.held_layers  # raises where they are not the lists', in order
        self.held  # raises where the share is outside the router's outputs
        for kind in set(self.layer_types):
            if len({h for h, t in zip(self.heads_per_layer, self.layer_types)
                    if t == kind}) != 1:
                raise NotImplementedError(
                    f"{kind} layers with different numbers of heads: a "
                    "kind's layers are stacked")
            if len({r for r, t in zip(self.rope_layout or (), self.layer_types)
                    if t == kind}) > 1:
                raise NotImplementedError(
                    f"{kind} layers with and without a rotary embedding: a "
                    "kind's layers are stacked")
        self.period  # raises where the pattern has none

    @staticmethod
    def tiny(vocab_size: int = 512, **over) -> "WindowMoeConfig":
        """12 layers in the published pattern (a dense full layer, then
        sliding x 3 : full), 6 : 4 query heads over 2 KV heads."""
        rope = {FULL: dict(PUBLISHED_ROPE[FULL], factor=4, beta_fast=8,
                           original_max_position_embeddings=16,
                           rope_theta=10_000),
                SLIDING: PUBLISHED_ROPE[SLIDING]}
        return WindowMoeConfig(**{**dict(
            vocab_size=vocab_size, d_model=64,
            layer_types=tuple(FULL if i % 4 == 0 else SLIDING
                              for i in range(12)),
            heads_per_layer=tuple(4 if i % 4 == 0 else 6 for i in range(12)),
            mlp_layer_types=(DENSE,) + (SPARSE,) * 11, n_kv_heads=2,
            d_head=16, window=8, rope_parameters=rope, d_ff=128,
            d_ff_expert=32, d_ff_shared=32, n_experts=16, n_experts_held=16,
            experts_per_token=4), **over})

    @staticmethod
    def tiny_ahead(vocab_size: int = 512, n: int = 8,
                   **over) -> "WindowMoeConfig":
        """`tiny` in SmallThinker's pattern: the published 0 / 1 lists (full,
        window, window, window; RoPE where the window is) over `n` layers, 7
        query heads over ONE KV head in both kinds, no dense layer, no gate,
        the router on the attention's input, softmax over the chosen, ReGLU
        experts and no shared one; published layers 0-3 held."""
        layout = tuple(int(i % 4 != 0) for i in range(n))
        theta = {"rope_theta": 1_500_000}
        return WindowMoeConfig.tiny(vocab_size, **{**dict(
            layer_types=layout, rope_layout=layout,
            heads_per_layer=(7,) * n, mlp_layer_types=(SPARSE,) * n,
            n_kv_heads=1, rope_parameters={FULL: theta, SLIDING: theta},
            attn_gate=False, d_ff_shared=0, score="softmax",
            routed_scaling_factor=1.0, router_input=ATTENTION_INPUT,
            expert_form="reglu", layers=(0, 1, 2, 3)), **over})

    @property
    def held_layers(self) -> Tuple[int, ...]:
        return layer_pattern.held_layers(self.layers, len(self.layer_types))

    @property
    def n_dense_layers(self) -> int:
        """The LEADING dense layers; a dense one further on is refused."""
        n = next((i for i, t in enumerate(self.mlp_layer_types)
                  if t != DENSE), len(self.mlp_layer_types))
        if DENSE in self.mlp_layer_types[n:]:
            raise NotImplementedError("a dense layer after a sparse one")
        return n

    @property
    def period(self) -> int:
        """Layers from one full layer to the next; a model of one kind of
        attention, or of a pattern that does not repeat so, is refused."""
        full = [i for i, t in enumerate(self.layer_types) if t == FULL]
        steps = {b - a for a, b in zip(full, full[1:])}
        if len(steps) != 1 or len(full) == len(self.layer_types):
            raise NotImplementedError(
                f"full attention at layers {full}: no one period")
        return steps.pop()

    def plan(self):
        """`layer_pattern.segments` of the held layers: a whole period is
        `period` consecutive held sparse layers that END with a full one."""
        first_full = self.layer_types.index(FULL)
        return layer_pattern.segments(
            self.held_layers, self.n_dense_layers, self.period,
            start=first_full + 1)

    def kind(self, i: int) -> Tuple[str, str]:
        """-> (attention, feed-forward) of published layer i, as the
        parameter stacks are named: ("full" | "sliding", "dense" | "sparse")."""
        return _SHORT[self.layer_types[i]], self.mlp_layer_types[i]

    def heads(self, attn: str) -> int:
        return next(h for h, t in zip(self.heads_per_layer, self.layer_types)
                    if _SHORT[t] == attn)

    def rotary(self, attn: str) -> Rotary:
        """The kind's rotary form; theta 0, which `blocks.qkv` reads as no
        rotary embedding, where `rope_layout` gives the kind's layers 0."""
        layer_type = FULL if attn == "full" else SLIDING
        if self.rope_layout is not None and not next(
                r for r, t in zip(self.rope_layout, self.layer_types)
                if t == layer_type):
            return Rotary(0.0)
        group = dict(dict(self.rope_parameters)[layer_type])
        width = int(self.d_head * group.get("partial_rotary_factor", 1))
        kind = group.get("rope_type", "default")
        if kind not in ("default", "yarn"):
            raise NotImplementedError(f"rope_type {kind!r}")
        yarn = None if kind == "default" else (
            group["factor"], group["original_max_position_embeddings"],
            group.get("beta_fast", 32), group.get("beta_slow", 1))
        return Rotary(float(group["rope_theta"]), width, yarn,
                      float(group.get("attention_factor", 1.0)))

    def num_params(self) -> int:
        c = self
        total = 2 * c.vocab_size * c.d_model + c.d_model
        for i in c.held_layers:
            attn, mlp = c.kind(i)
            total += attn_num_params(c, c.heads(attn)) + 2 * c.d_model
            total += 3 * c.d_model * c.d_ff if mlp == DENSE else (
                c.d_model * c.n_experts + 3 * c.d_model
                * (c.n_experts_held * c.d_ff_expert + c.d_ff_shared))
        return total


def attn_num_params(c, heads: int) -> int:
    """The attention's parameters (no layer norm) at `heads` query heads."""
    return (2 * c.d_model * c.d_head * (heads + c.n_kv_heads)
            + (c.d_model * heads if c.attn_gate else 0)
            + (2 * c.d_head if c.qk_norm else 0))


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _layer_axes(L, config, mlp: str):
    axes = {**blocks.attn_axes(L), "mlp_norm": L + (None,)}
    if config.attn_gate:
        axes["w_attn_gate"] = L + ("embed", "heads")
    if config.qk_norm:
        axes.update(q_norm=L + (None,), k_norm=L + (None,))
    if mlp == DENSE:
        return {**axes, **blocks.ffn_axes(L)}
    routed = experts.routed_axes(L)
    del routed["router_bias"]  # the choice is on the scores themselves
    if not config.d_ff_shared:
        del routed["shared"]
    return {**axes, **routed}


def _stacks(config):
    """-> ({(attention, feed-forward): unrolled layers of that kind}, the
    scanned periods' number). A kind's unrolled layers are stacked under
    `params["loose"]["<attention>_<feed-forward>"]`."""
    dense, loose, periods, _ = config.plan()
    one = {}
    for i in dense + loose:
        one[config.kind(i)] = one.get(config.kind(i), 0) + 1
    return one, len(periods)


def param_logical_axes(config: WindowMoeConfig) -> Dict[str, Any]:
    c = config
    L = ("layers",)
    one, periods = _stacks(c)
    axes = {"embed": ("vocab", "embed"), "final_norm": (None,),
            "lm_head": ("embed", "vocab")}
    if one:
        axes["loose"] = {f"{attn}_{mlp}": _layer_axes(L, c, mlp)
                         for attn, mlp in one}
    if periods:
        axes["periods"] = {"sliding": _layer_axes(L + (None,), c, SPARSE),
                           "full": _layer_axes(L, c, SPARSE)}
    return axes


def _init_layer(config, attn: str, mlp: str, key):
    """One layer: `mla_moe.init`'s rules (fan-in scaled normal matrices,
    norm scales 1, the router 0.02 normal), at the kind's query heads."""
    c = config
    d, dh, heads = c.d_model, c.d_head, c.heads(attn)
    ones = partial(jnp.ones, dtype=c.dtype)
    dense = partial(blocks.dense, c)
    ks = jax.random.split(key, 12)
    p = {"attn_norm": ones((d,)),
         "wq": dense(ks[0], (d, heads, dh), d),
         "wk": dense(ks[1], (d, c.n_kv_heads, dh), d),
         "wv": dense(ks[2], (d, c.n_kv_heads, dh), d),
         "wo": dense(ks[3], (heads, dh, d), heads * dh),
         "mlp_norm": ones((d,))}
    if c.attn_gate:
        p["w_attn_gate"] = dense(ks[4], (d, heads), d)
    if c.qk_norm:
        p.update(q_norm=ones((dh,)), k_norm=ones((dh,)))
    if mlp == DENSE:
        return {**p, **blocks.init_ffn(c, ks[5:8], (), c.d_ff)}
    p.update(router=(jax.random.normal(ks[5], (d, c.n_experts))
                     * 0.02).astype(c.dtype),
             experts=blocks.init_ffn(c, ks[6:9], (c.n_experts_held,),
                                     c.d_ff_expert))
    if c.d_ff_shared:
        p["shared"] = blocks.init_ffn(c, ks[9:12], (), c.d_ff_shared)
    return p


def init(config: WindowMoeConfig, key) -> Dict[str, Any]:
    """`_init_layer`'s rules; the embedding's rows N(0, 1) (`mla_moe.init`
    on why), the head fan-in scaled."""
    c = config
    one, periods = _stacks(c)
    k_embed, k_head, k_one, k_periods = jax.random.split(key, 4)
    stack = lambda attn, mlp, key, n: jax.vmap(  # noqa: E731
        partial(_init_layer, c, attn, mlp))(jax.random.split(key, n))
    params = {
        "embed": blocks.dense(c, k_embed, (c.vocab_size, c.d_model), 1),
        "final_norm": jnp.ones((c.d_model,), c.dtype),
        "lm_head": blocks.dense(c, k_head, (c.d_model, c.vocab_size),
                                c.d_model)}
    if one:
        params["loose"] = {
            f"{attn}_{mlp}": stack(attn, mlp, jax.random.fold_in(k_one, j), n)
            for j, ((attn, mlp), n) in enumerate(sorted(one.items()))}
    if periods:
        k_sliding, k_full = jax.random.split(k_periods)
        params["periods"] = {
            "sliding": jax.vmap(
                lambda k: stack("sliding", SPARSE, k, c.period - 1))(
                    jax.random.split(k_sliding, periods)),
            "full": stack("full", SPARSE, k_full, periods)}
    return params


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def layer(x, p, positions, config, mesh, rules, attn: str, mlp: str):
    """One layer -> (x, the chosen experts [B * S, k] or None)."""
    c = config
    mask = None
    if attn == "sliding":
        mask = SlidingWindow(c.window)
    ahead = None
    if mlp == SPARSE and c.router_input != FFN_INPUT:
        # the router ahead of the attention: on what the attention reads
        # (`blocks.qkv`'s normed input: the compiler keeps one) or on the
        # residual itself, so the choice is known before attention runs
        h = x if c.router_input == RESIDUAL \
            else rms_norm(x, p["attn_norm"], c.norm_eps)
        ahead = experts.routing(h.reshape(-1, c.d_model), p, c)
        device_profiler.count("moe.routed_ahead", 1)  # per lowering
    x = blocks.attn_sublayer(x, p, positions, c, mesh, rules, mask=mask,
                             rotary=c.rotary(attn))
    if mlp == DENSE:
        return blocks.mlp_sublayer(x, p, c, mesh, rules), None
    return experts.expert_sublayer(x, p, c, mesh, rules, ahead=ahead,
                                   form=c.expert_form)


def forward_hidden(params, tokens, config: WindowMoeConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> (final-norm hidden states [B, S, D], the chosen
    experts of every sparse layer [L, B * S, k], in the layers' order)."""
    c = config
    x, positions = blocks.embed_tokens(params, tokens, mesh, rules)
    x = residual(x.astype(c.dtype), mesh, rules)
    # a kind of layer is named as its unrolled stack is; a period's two
    # stacks, "sliding" and "full", hold sparse layers. A wrapper a layer:
    # every unrolled layer is traced on its own
    body = lambda kind: blocks.checkpointed(partial(  # noqa: E731
        layer, positions=positions, config=c, mesh=mesh, rules=rules,
        attn=kind.split("_")[0], mlp=kind.split("_")[1]), c)
    dense, loose, _, segments = c.plan()
    x, chosen = layer_pattern.walk(
        x, segments, ["_".join(c.kind(i)) for i in dense + loose],
        params.get("loose"),
        {f"{attn}_{SPARSE}": stack
         for attn, stack in params.get("periods", {}).items()},
        [(f"sliding_{SPARSE}", c.period - 1), (f"full_{SPARSE}", None)], body)
    return rms_norm(x, params["final_norm"], c.norm_eps), chosen


def loss_fn(params, batch, config: WindowMoeConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """Next-token CE (`blocks.next_token_loss`), masked by batch["mask"]
    when given. Scalar return (make_train_step contract)."""
    return blocks.next_token_loss(forward_hidden, None, params, batch, config,
                                  mesh, rules)
