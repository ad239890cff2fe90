"""A decoder whose layers are ONE sublayer each, of three kinds in a published
pattern: Mamba-2 state-space layers (`M`), experts that work in a latent
narrower than the residual (`E`) and GQA attention without a rotary
embedding (`*`); an MTP block on top (NVIDIA-Nemotron-3-Super-120B-A12B by
config: `model_type` `nemotron_h`), TPU-first, training only.

Every layer is x = x + f(RMSNorm(x)), over the layer library:
`models/blocks.py` (RMSNorm, the attention sublayer, remat, the loss and the
MTP loss), `models/mixers.py` (Mamba-2 and the parameters of the `M` and `*`
layers, which `granite_hybrid` shares), `models/layer_pattern.py` (the walk)
and `parallel/moe.moe_layer`; the latent experts are this module's own. With
h = RMSNorm(x):

- `M`: `mixers.mamba_sublayer` (Mamba-2, arXiv:2405.21060, through
  `ops/ssd.py`; its docstring has the recurrence).
- `*`: `blocks.attn_sublayer`, causal, `n_heads` query and `n_kv_heads` KV
  heads, scale d_head ** -0.5, `rope_theta` 0: no rotary embedding.
- `E` (LatentMoE): s = sigmoid(h W_r), float32; the choice is the top k of
  s + bias (no gradient); weights the unbiased s of the chosen, normalised,
  x `routed_scaling_factor` (`parallel/moe.route`). u = h W_down
  (`d_model` -> `latent_size`); each chosen expert gives W2_e relu(W1_e u)^2
  in the latent; their weighted sum goes back through W_up; plus the shared
  expert W2_s relu(W1_s h)^2 on h itself, at the residual's width.
- MTP, one block (`mtp_pattern`, `*E`): `mla_moe.py`'s (DeepSeek-V3's) form,
  [RMSNorm(Emb(t_{i+1})) | RMSNorm(h_i)] W_eh -> the pattern's layers ->
  its own final norm and the SHARED head, predicting t_{i+2}; loss = CE +
  `mtp_loss_coef` CE_mtp.

`layers` lists the published indices this program holds, in order (all of
the pattern's by default: the whole model). Where they hold two or more
consecutive (`E`, `M`) pairs (`plan`), `layer_pattern.walk` scans those: the
published pattern is a `*` and four or five such pairs to a period, so three
layer bodies are traced whatever the depth. The other layers are unrolled.
Remat is per layer, the flash call's `o` and `lse` and the SSD call's `y`
saved.

The share: `experts.py`'s (`n_experts_held`, `first_expert`).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler
from ray_tpu.models import blocks, experts, layer_pattern, mixers
from ray_tpu.models.blocks import residual, rms_norm
from ray_tpu.ops import ssd as ssd_op
from ray_tpu.ops.flash_attention import RESIDUAL_NAMES as FLASH_RESIDUALS
from ray_tpu.parallel.moe import moe_layer
from ray_tpu.parallel.sharding import LogicalAxisRules

PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
KINDS = {"M": "mamba", "E": "experts", "*": "attn"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(experts.Share):
    """`pattern`: a layer's kind by its published index (`M`, `E`, `*`);
    `layers`: the published indices held here (None: all). The attention
    fields carry `llama.LlamaConfig`'s names: `blocks.attn_sublayer` reads
    them."""
    vocab_size: int = 131_072
    d_model: int = 4096
    pattern: str = PUBLISHED_PATTERN
    layers: Optional[Tuple[int, ...]] = None
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    state_size: int = 128
    n_groups: int = 8
    conv_size: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    n_heads: int = 32
    n_kv_heads: int = 2
    d_head: int = 128
    rope_theta: float = 0.0        # 0: no rotary embedding
    latent_size: int = 1024
    d_ff_expert: int = 2688        # ONE expert's width, in the latent
    d_ff_shared: int = 5376
    n_experts: int = 512           # the router's outputs
    n_experts_held: int = 512      # of them, the experts this program holds
    first_expert: int = 0
    experts_per_token: int = 22
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    norm_eps: float = 1e-5
    mtp_depth: int = 1
    mtp_pattern: str = "*E"
    mtp_loss_coef: float = 0.1
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "dots"
    loss_chunk_size: int = 0
    # what `blocks.attn_sublayer` also reads of its config: constants here
    qk_norm = False
    use_ring_attention = False

    def __post_init__(self):
        if self.layers is not None and not isinstance(self.layers, tuple):
            object.__setattr__(self, "layers", tuple(self.layers))
        self.held_layers  # raises where they are not the pattern's, in order
        if set(self.pattern + self.mtp_pattern) - set(KINDS):
            raise ValueError(f"a layer is one of {sorted(KINDS)}")
        if self.mtp_depth not in (0, 1):
            raise ValueError("mtp_depth is 0 or 1")
        if self.chunk_size != ssd_op.CHUNK:
            raise ValueError(f"ops/ssd.py walks chunks of {ssd_op.CHUNK}")
        if self.mamba_heads % self.n_groups:
            raise ValueError("n_groups does not divide the Mamba heads")
        self.held  # raises where the share is outside the router's outputs

    @staticmethod
    def tiny(vocab_size: int = 512, **over) -> "NemotronHConfig":
        return NemotronHConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, pattern="M*EMEMEM*EME",
            mamba_heads=4, mamba_head_dim=16, state_size=8, n_groups=2,
            n_heads=4, n_kv_heads=2, d_head=16, latent_size=32,
            d_ff_expert=24, d_ff_shared=48, n_experts=16, n_experts_held=16,
            experts_per_token=4), **over})

    @property
    def held_layers(self) -> Tuple[int, ...]:
        return layer_pattern.held_layers(self.layers, len(self.pattern))

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size

    def plan(self):
        """The held layers in execution order, as segments: ("pairs", i, n)
        is n >= 2 consecutive (`E`, `M`) pairs from published index i on,
        run as one scan; ("one", kind, i) a layer unrolled."""
        held, kinds = self.held_layers, self.pattern
        segments, j = [], 0
        while j < len(held):
            n = 0
            while (j + 2 * n + 1 < len(held)
                   and held[j + 2 * n + 1] == held[j] + 2 * n + 1
                   and held[j + 2 * n] == held[j] + 2 * n
                   and kinds[held[j] + 2 * n:held[j] + 2 * n + 2] == "EM"):
                n += 1
            if n >= 2:
                segments.append(("pairs", held[j], n))
                j += 2 * n
            else:
                segments.append(("one", kinds[held[j]], held[j]))
                j += 1
        return segments

    def num_params(self) -> int:
        c = self
        per = {k: layer_num_params(c, k) + c.d_model for k in KINDS}
        mtp = c.mtp_depth * (2 * c.d_model * c.d_model + 3 * c.d_model
                             + sum(per[k] for k in c.mtp_pattern))
        return (2 * c.vocab_size * c.d_model + c.d_model + mtp
                + sum(per[c.pattern[i]] for i in c.held_layers))


def layer_num_params(c, kind: str) -> int:
    """One layer's parameters less its norm's d_model."""
    if kind != "E":
        return mixers.mixer_num_params(c, kind)
    d = c.d_model
    return (d * c.n_experts + c.n_experts + 2 * d * c.latent_size
            + 2 * d * c.d_ff_shared
            + c.n_experts_held * 2 * c.latent_size * c.d_ff_expert)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _layer_axes(L, kind: str):
    if kind != "E":
        return mixers.mixer_axes(L, kind)
    # the held experts' dim is NOT the `ep` axis's: a share has no exchange
    return {"mlp_norm": L + (None,), "router": L + ("embed", None),
            "router_bias": L + (None,), "w_latent_in": L + ("embed", None),
            "w_latent_out": L + (None, "embed"),
            "experts": {"w_up": L + (None, None, "mlp"),
                        "w_down": L + (None, "mlp", None)},
            "shared": {"w_up": L + ("embed", "mlp"),
                       "w_down": L + ("mlp", "embed")}}


def _stacks(config):
    """-> ({kind: unrolled layers of it}, the scanned pairs' number)."""
    one, pairs = {}, 0
    for seg in config.plan():
        if seg[0] == "pairs":
            pairs += seg[2]
        else:
            one[seg[1]] = one.get(seg[1], 0) + 1
    return one, pairs


def param_logical_axes(config: NemotronHConfig) -> Dict[str, Any]:
    c = config
    L = ("layers",)
    one, pairs = _stacks(c)
    axes = {"embed": ("vocab", "embed"), "final_norm": (None,),
            "lm_head": ("embed", "vocab")}
    if one:
        axes["one"] = {KINDS[k]: _layer_axes(L, k) for k in one}
    if pairs:
        axes["pairs"] = {KINDS[k]: _layer_axes(L, k) for k in "EM"}
    if c.mtp_depth:
        axes["mtp"] = {
            "enorm": (None,), "hnorm": (None,), "eh_proj": (None, "embed"),
            "block": {str(j): _layer_axes((), k)
                      for j, k in enumerate(c.mtp_pattern)},
            "final_norm": (None,)}
    return axes


def _init_layer(config, kind: str, key):
    """One layer: `mixers.init_mixer`'s `M` and `*`; `E`: fan-in scaled
    normal matrices (`blocks.dense`), norm scales 1, the router 0.02 normal,
    its bias float32 N(0, 0.01^2) (`mla_moe.init` on why not zero)."""
    c = config
    if kind != "E":
        return mixers.init_mixer(c, kind, key)
    d = c.d_model
    ones = partial(jnp.ones, dtype=c.dtype)
    dense = partial(blocks.dense, c)
    ks = jax.random.split(key, 8)
    held, lat = c.n_experts_held, c.latent_size
    return {
        "mlp_norm": ones((d,)),
        "router": (jax.random.normal(ks[0], (d, c.n_experts)) * 0.02).astype(
            c.dtype),
        "router_bias": jax.random.normal(ks[1], (c.n_experts,)) * 0.01,
        "w_latent_in": dense(ks[2], (d, lat), d),
        "w_latent_out": dense(ks[3], (lat, d), lat),
        "experts": {"w_up": dense(ks[4], (held, lat, c.d_ff_expert), lat),
                    "w_down": dense(ks[5], (held, c.d_ff_expert, lat),
                                    c.d_ff_expert)},
        "shared": {"w_up": dense(ks[6], (d, c.d_ff_shared), d),
                   "w_down": dense(ks[7], (c.d_ff_shared, d), c.d_ff_shared)}}


def init(config: NemotronHConfig, key) -> Dict[str, Any]:
    """`_init_layer`'s rules; the embedding's rows N(0, 1) (`mla_moe.init`
    on why), the head fan-in scaled."""
    c = config
    one, pairs = _stacks(c)
    k_embed, k_head, k_one, k_pairs, k_mtp = jax.random.split(key, 5)
    stack = lambda kind, key, n: jax.vmap(  # noqa: E731
        partial(_init_layer, c, kind))(jax.random.split(key, n))
    params = {
        "embed": blocks.dense(c, k_embed, (c.vocab_size, c.d_model), 1),
        "final_norm": jnp.ones((c.d_model,), c.dtype),
        "lm_head": blocks.dense(c, k_head, (c.d_model, c.vocab_size),
                                c.d_model)}
    if one:
        params["one"] = {
            KINDS[k]: stack(k, jax.random.fold_in(k_one, ord(k)), n)
            for k, n in one.items()}
    if pairs:
        params["pairs"] = {
            KINDS[k]: stack(k, jax.random.fold_in(k_pairs, ord(k)), pairs)
            for k in "EM"}
    if c.mtp_depth:
        k_proj, k_block = jax.random.split(k_mtp)
        params["mtp"] = {
            "enorm": jnp.ones((c.d_model,), c.dtype),
            "hnorm": jnp.ones((c.d_model,), c.dtype),
            "eh_proj": blocks.dense(c, k_proj, (2 * c.d_model, c.d_model),
                                    2 * c.d_model),
            "block": {str(j): _init_layer(c, k, jax.random.fold_in(k_block, j))
                      for j, k in enumerate(c.mtp_pattern)},
            "final_norm": jnp.ones((c.d_model,), c.dtype)}
    return params


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def expert_sublayer(x, p, config: NemotronHConfig, mesh=None,
                    rules: Optional[LogicalAxisRules] = None):
    """x [B, S, D] -> (x + the routed experts' part, through the latent, +
    the shared expert's, of RMSNorm(x); the chosen experts [B * S, k])."""
    c = config
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        raise NotImplementedError(
            "nemotron_h runs its experts in one program (all of them, or "
            "one chip's share without the exchange): no `ep` mesh axis")
    b, s, d = x.shape
    relu2 = lambda u: jnp.square(jax.nn.relu(u.astype(jnp.float32))).astype(  # noqa: E731
        c.dtype)
    h = rms_norm(x, p["mlp_norm"], c.norm_eps)
    flat = h.reshape(b * s, d)
    with jax.named_scope("moe.latent"):
        latent = flat @ p["w_latent_in"]
    routed, aux = moe_layer(
        flat, p["router"], p["experts"], c.experts_per_token,
        c.norm_topk_prob, score="sigmoid", router_bias=p["router_bias"],
        weight_scale=c.routed_scaling_factor, held=c.held,
        n_group=c.n_group, topk_group=c.topk_group, form="relu2",
        rows=latent)
    with jax.named_scope("moe.latent"):
        routed = routed @ p["w_latent_out"]
    with jax.named_scope("moe.shared"):
        shared = relu2(h @ p["shared"]["w_up"]) @ p["shared"]["w_down"]
    x = x + routed.reshape(b, s, d) + shared
    return residual(x, mesh, rules), aux.experts


def layer(x, p, positions, config, mesh, rules, kind: str):
    """One layer -> (x, the chosen experts [B * S, k] or None)."""
    if kind == "E":
        return expert_sublayer(x, p, config, mesh, rules)
    if kind == "M":
        return mixers.mamba_sublayer(x, p, config, mesh, rules, None), None
    return blocks.attn_sublayer(x, p, positions, config, mesh, rules), None


def bodies(config, positions, mesh, rules):
    """-> {a kind's name, as its parameters': the layer of that kind under
    the remat policy, the flash
    call's and the SSD call's outputs saved beside what it saves}."""
    return {name: blocks.checkpointed(
        partial(layer, positions=positions, config=config, mesh=mesh,
                rules=rules, kind=kind), config,
        FLASH_RESIDUALS + ssd_op.RESIDUAL_NAMES)
        for kind, name in KINDS.items()}


def forward_hidden(params, tokens, config: NemotronHConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> (final-norm hidden states [B, S, D], the chosen
    experts of every expert layer [L, B * S, k], in the layers' order)."""
    c = config
    x, positions = blocks.embed_tokens(params, tokens, mesh, rules)
    x = residual(x.astype(c.dtype), mesh, rules)
    plan = c.plan()
    x, chosen = layer_pattern.walk(
        x, [("periods", seg[2]) if seg[0] == "pairs" else ("one", 1)
            for seg in plan],
        [KINDS[seg[1]] for seg in plan if seg[0] == "one"],
        params.get("one"), params.get("pairs"),
        [("experts", None), ("mamba", None)],
        bodies(c, positions, mesh, rules).__getitem__)
    return rms_norm(x, params["final_norm"], c.norm_eps), chosen


def mtp_hidden(params, hidden, next_tokens, config: NemotronHConfig,
               mesh=None, rules: Optional[LogicalAxisRules] = None):
    """The MTP block (`mla_moe.mtp_hidden`'s form over this model's
    layers). hidden [B, S, D]: the main model's final-norm output h_i;
    next_tokens [B, S]: t_{i+1} -> (its own final-norm hidden states, from
    which the shared lm_head predicts t_{i+2}; the block's chosen experts
    [expert layers, B * S, k])."""
    c = config
    p = params["mtp"]
    b, s = next_tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    with jax.named_scope("mtp.block"):
        emb = blocks.embed_rows(params["embed"], next_tokens,
                                mesh).astype(c.dtype)
        x = jnp.concatenate([rms_norm(emb, p["enorm"], c.norm_eps),
                             rms_norm(hidden, p["hnorm"], c.norm_eps)],
                            axis=-1) @ p["eh_proj"]
        x = residual(x, mesh, rules)
        body = bodies(c, positions, mesh, rules)
        chosen = []
        for j, kind in enumerate(c.mtp_pattern):
            x, e = body[KINDS[kind]](x, p["block"][str(j)])
            if e is not None:
                chosen.append(e)
        device_profiler.count("mtp.depth", 1)  # per lowering
        return rms_norm(x, p["final_norm"], c.norm_eps), jnp.stack(chosen)


def loss_fn(params, batch, config: NemotronHConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """Next-token CE + `mtp_loss_coef` * the MTP block's CE of the token
    after (`blocks.next_token_loss`). Scalar return."""
    return blocks.next_token_loss(forward_hidden, mtp_hidden, params, batch,
                                  config, mesh, rules)
