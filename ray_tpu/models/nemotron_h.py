"""A decoder whose layers are ONE sublayer each, of three kinds in a published
pattern: Mamba-2 state-space layers (`M`), experts that work in a latent
narrower than the residual (`E`) and GQA attention without a rotary
embedding (`*`); an MTP block on top (NVIDIA-Nemotron-3-Super-120B-A12B by
config: `model_type` `nemotron_h`), TPU-first, training only.

Every layer is x = x + f(RMSNorm(x)), sharing `models/llama.py`'s RMSNorm,
attention sublayer, remat policy and chunked cross-entropy, `mla_moe.py`'s
MTP form and `parallel/moe.moe_layer`. With h = RMSNorm(x):

- `M` (Mamba-2, arXiv:2405.21060; `ops/ssd.py`): [z | xBC | dt] = h W_in,
  widths H P | H P + 2 G N | H. xBC = SiLU(causal depthwise conv over time,
  `conv_size` taps, with bias), split into x (H heads x P), B and C (G groups
  x N; head j reads group j // (H / G)). Delta = softplus(dt + dt_bias) a
  head, a = -exp(A_log) Delta. State H in R^{P x N} a head, float32, H_0 = 0:
      H_t = exp(a_t) H_{t-1} + Delta_t x_t B_t^T,   y_t = H_t C_t + D x_t
  f = [RMSNorm_group(y * SiLU(z))] W_out, the norm over each of the G
  groups' H P / G channels with one scale [H P] (the gate BEFORE the norm).
- `*`: `llama._attn_sublayer`, causal, `n_heads` query and `n_kv_heads` KV
  heads, scale d_head ** -0.5, `rope_theta` 0: no rotary embedding.
- `E` (LatentMoE): s = sigmoid(h W_r), float32; the choice is the top k of
  s + bias (no gradient); weights the unbiased s of the chosen, normalised,
  x `routed_scaling_factor` (`parallel/moe.route`). u = h W_down
  (`d_model` -> `latent_size`); each chosen expert gives W2_e relu(W1_e u)^2
  in the latent; their weighted sum goes back through W_up; plus the shared
  expert W2_s relu(W1_s h)^2 on h itself, at the residual's width.
- MTP, one block (`mtp_pattern`, `*E`): `mla_moe.py`'s form,
  [RMSNorm(Emb(t_{i+1})) | RMSNorm(h_i)] W_eh -> the pattern's layers ->
  its own final norm and the SHARED head, predicting t_{i+2}; loss = CE +
  `mtp_loss_coef` CE_mtp.

`layers` lists the published indices this program holds, in order (all of
the pattern's by default: the whole model). Where they hold two or more
consecutive (`E`, `M`) pairs, those run as ONE `lax.scan` over the stacked
pairs (`plan`): the published pattern is a `*` and four or five such pairs
to a period, so three layer bodies are traced whatever the depth. The other
layers are unrolled. Remat is per layer; the flash call's `o` and `lse` and
the SSD call's `y` are saved beside what the policy saves.

The share: `mla_moe`'s (`n_experts_held`, `first_expert`).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler
from ray_tpu.models import llama, mla_moe
from ray_tpu.models.llama import _residual, _rms_norm
from ray_tpu.ops import ssd as ssd_op
from ray_tpu.ops.flash_attention import RESIDUAL_NAMES as FLASH_RESIDUALS
from ray_tpu.parallel.moe import moe_layer
from ray_tpu.parallel.sharding import LogicalAxisRules, with_logical_constraint

PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
_KINDS = {"M": "mamba", "E": "experts", "*": "attn"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """`pattern`: a layer's kind by its published index (`M`, `E`, `*`);
    `layers`: the published indices held here (None: all). The attention
    fields carry `llama.LlamaConfig`'s names, whose sublayer reads them."""
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
    # what `llama._attn_sublayer` also reads of its config: constants here
    qk_norm = False
    use_ring_attention = False

    def __post_init__(self):
        if self.layers is not None and not isinstance(self.layers, tuple):
            object.__setattr__(self, "layers", tuple(self.layers))
        held = self.held_layers
        if list(held) != sorted(set(held)) or not held \
                or not 0 <= held[0] <= held[-1] < len(self.pattern):
            raise ValueError(f"layers {held} of {len(self.pattern)}")
        if set(self.pattern + self.mtp_pattern) - set(_KINDS):
            raise ValueError(f"a layer is one of {sorted(_KINDS)}")
        if self.mtp_depth not in (0, 1):
            raise ValueError("mtp_depth is 0 or 1")
        if self.chunk_size != ssd_op.CHUNK:
            raise ValueError(f"ops/ssd.py walks chunks of {ssd_op.CHUNK}")
        if self.mamba_heads % self.n_groups:
            raise ValueError("n_groups does not divide the Mamba heads")
        if not (0 <= self.first_expert
                and self.first_expert + self.n_experts_held <= self.n_experts):
            raise ValueError("held experts outside the router's outputs")

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
        return self.layers if self.layers is not None \
            else tuple(range(len(self.pattern)))

    @property
    def held(self):
        """`moe_layer`'s `held`: None where every expert is here."""
        if self.n_experts_held == self.n_experts:
            return None
        return self.first_expert, self.n_experts_held

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
        per = {k: layer_num_params(c, k) + c.d_model for k in _KINDS}
        mtp = c.mtp_depth * (2 * c.d_model * c.d_model + 3 * c.d_model
                             + sum(per[k] for k in c.mtp_pattern))
        return (2 * c.vocab_size * c.d_model + c.d_model + mtp
                + sum(per[c.pattern[i]] for i in c.held_layers))


def layer_num_params(c, kind: str) -> int:
    """One layer's parameters less its norm's d_model."""
    d = c.d_model
    if kind == "M":
        return (d * (c.d_inner + c.conv_dim + c.mamba_heads)
                + (c.conv_size + 1) * c.conv_dim + 3 * c.mamba_heads
                + c.d_inner + c.d_inner * d)
    if kind == "*":
        return 2 * d * c.d_head * (c.n_heads + c.n_kv_heads)
    return (d * c.n_experts + c.n_experts + 2 * d * c.latent_size
            + 2 * d * c.d_ff_shared
            + c.n_experts_held * 2 * c.latent_size * c.d_ff_expert)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _layer_axes(L, kind: str):
    if kind == "M":
        return {"norm": L + (None,), "w_in": L + ("embed", None),
                "conv_w": L + (None, None), "conv_b": L + (None,),
                "a_log": L + (None,), "d_skip": L + (None,),
                "dt_bias": L + (None,), "gate_norm": L + (None,),
                "w_out": L + (None, "embed")}
    if kind == "*":
        proj = L + ("embed", "heads", "kv")
        return {"attn_norm": L + (None,), "wq": proj, "wk": proj, "wv": proj,
                "wo": L + ("heads", "kv", "embed")}
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
        axes["one"] = {_KINDS[k]: _layer_axes(L, k) for k in one}
    if pairs:
        axes["pairs"] = {_KINDS[k]: _layer_axes(L, k) for k in "EM"}
    if c.mtp_depth:
        axes["mtp"] = {
            "enorm": (None,), "hnorm": (None,), "eh_proj": (None, "embed"),
            "block": {str(j): _layer_axes((), k)
                      for j, k in enumerate(c.mtp_pattern)},
            "final_norm": (None,)}
    return axes


def _init_layer(config, kind: str, key):
    """One layer. Fan-in scaled normal matrices (`mla_moe._dense`), norm
    scales 1. `M`: conv taps N(0, 1 / conv_size), its bias N(0, 0.02^2);
    `A_log` = log U(1, 16) a head, D = 1, `dt_bias` the inverse softplus of
    a step drawn log-uniform in [`time_step_min`, `time_step_max`] and kept
    above `time_step_floor` (Mamba-2's own initialisation): a head's decay
    a token runs from exp(-0.001) to exp(-1.6). `E`: the router 0.02 normal,
    its bias float32 N(0, 0.01^2) (`mla_moe.init` on why not zero)."""
    c = config
    d = c.d_model
    ones = partial(jnp.ones, dtype=c.dtype)
    dense = partial(mla_moe._dense, c)
    ks = jax.random.split(key, 8)
    if kind == "M":
        h = c.mamba_heads
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            ks[2], (h,), minval=math.log(c.time_step_min),
            maxval=math.log(c.time_step_max))), c.time_step_floor)
        return {
            "norm": ones((d,)),
            "w_in": dense(ks[0], (d, c.d_inner + c.conv_dim + h), d),
            "conv_w": dense(ks[1], (c.conv_size, c.conv_dim), c.conv_size),
            "conv_b": (jax.random.normal(ks[5], (c.conv_dim,)) * 0.02).astype(
                c.dtype),
            "a_log": jnp.log(jax.random.uniform(ks[3], (h,), minval=1.0,
                                                maxval=16.0)),
            "d_skip": jnp.ones((h,), jnp.float32),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "gate_norm": ones((c.d_inner,)),
            "w_out": dense(ks[4], (c.d_inner, d), c.d_inner)}
    if kind == "*":
        return {
            "attn_norm": ones((d,)),
            "wq": dense(ks[0], (d, c.n_heads, c.d_head), d),
            "wk": dense(ks[1], (d, c.n_kv_heads, c.d_head), d),
            "wv": dense(ks[2], (d, c.n_kv_heads, c.d_head), d),
            "wo": dense(ks[3], (c.n_heads, c.d_head, d), c.n_heads * c.d_head)}
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
        "embed": mla_moe._dense(c, k_embed, (c.vocab_size, c.d_model), 1),
        "final_norm": jnp.ones((c.d_model,), c.dtype),
        "lm_head": mla_moe._dense(c, k_head, (c.d_model, c.vocab_size),
                                  c.d_model)}
    if one:
        params["one"] = {
            _KINDS[k]: stack(k, jax.random.fold_in(k_one, ord(k)), n)
            for k, n in one.items()}
    if pairs:
        params["pairs"] = {
            _KINDS[k]: stack(k, jax.random.fold_in(k_pairs, ord(k)), pairs)
            for k in "EM"}
    if c.mtp_depth:
        k_proj, k_block = jax.random.split(k_mtp)
        params["mtp"] = {
            "enorm": jnp.ones((c.d_model,), c.dtype),
            "hnorm": jnp.ones((c.d_model,), c.dtype),
            "eh_proj": mla_moe._dense(c, k_proj, (2 * c.d_model, c.d_model),
                                      2 * c.d_model),
            "block": {str(j): _init_layer(c, k, jax.random.fold_in(k_block, j))
                      for j, k in enumerate(c.mtp_pattern)},
            "final_norm": jnp.ones((c.d_model,), c.dtype)}
    return params


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def _conv_silu(x, taps, bias):
    """x [B, S, C], taps [K, C], bias [C] -> SiLU of the causal depthwise
    conv over time: y_t = bias + sum_j taps[j] x_{t - (K - 1) + j}, zeros
    before 0; float32."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + s].astype(jnp.float32)
            * taps[j].astype(jnp.float32) for j in range(k))
    return jax.nn.silu(y + bias.astype(jnp.float32))


def mamba_mixer(h, p, config):
    """The Mamba-2 mixer of the module's docstring on h [B, S, D], a
    layer's normed input -> [B, S, D], before the residual is added (shared
    with `models/granite_hybrid.py`, which scales it first). `config` gives
    `mamba_heads`, `mamba_head_dim`, `n_groups`, `state_size`, `d_inner`,
    `conv_dim`, `chunk_size` (the scan's chunk), `norm_eps` and `dtype`."""
    c = config
    b, s, _ = h.shape
    heads, groups = c.mamba_heads, c.n_groups
    wide, gn = c.d_inner, c.n_groups * c.state_size
    f32 = jnp.float32
    with jax.named_scope("ssd.project"):
        zxbcdt = h @ p["w_in"]
        z = zxbcdt[..., :wide]
        xbc = _conv_silu(zxbcdt[..., wide:wide + c.conv_dim], p["conv_w"],
                         p["conv_b"]).astype(c.dtype)
        dt = zxbcdt[..., wide + c.conv_dim:]
    with jax.named_scope("ssd.scan"):
        y = ssd_op.ssd(
            xbc[..., :wide].reshape(b, s, heads, c.mamba_head_dim), dt,
            p["a_log"], xbc[..., wide:wide + gn].reshape(
                b, s, groups, c.state_size),
            xbc[..., wide + gn:].reshape(b, s, groups, c.state_size),
            p["d_skip"], p["dt_bias"], chunk=c.chunk_size)
    with jax.named_scope("ssd.gate"):
        gated = (y.reshape(b, s, wide).astype(f32)
                 * jax.nn.silu(z.astype(f32))).reshape(b, s, groups, -1)
        gated = gated * jax.lax.rsqrt(
            jnp.mean(gated * gated, axis=-1, keepdims=True) + c.norm_eps)
        gated = gated.reshape(b, s, wide).astype(c.dtype) * p["gate_norm"]
    device_profiler.count("ssd.layers", 1)  # per lowering
    return gated @ p["w_out"]


def _mamba_sublayer(x, p, config: NemotronHConfig, mesh=None,
                    rules: Optional[LogicalAxisRules] = None):
    """x [B, S, D] -> x + Mamba-2(RMSNorm(x)) (the module's docstring)."""
    h = _rms_norm(x, p["norm"], config.norm_eps)
    return _residual(x + mamba_mixer(h, p, config), mesh, rules)


def _expert_sublayer(x, p, config: NemotronHConfig, mesh=None,
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
    h = _rms_norm(x, p["mlp_norm"], c.norm_eps)
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
    return _residual(x, mesh, rules), aux.experts


def _layer(x, p, positions, config, mesh, rules, kind: str):
    """One layer -> (x, the chosen experts [B * S, k] or None)."""
    if kind == "E":
        return _expert_sublayer(x, p, config, mesh, rules)
    if kind == "M":
        return _mamba_sublayer(x, p, config, mesh, rules), None
    return llama._attn_sublayer(x, p, positions, config, mesh, rules), None


def _bodies(config, positions, mesh, rules):
    """-> {kind: the layer of that kind under the remat policy, the flash
    call's and the SSD call's outputs saved beside what it saves}."""
    return {kind: mla_moe._checkpointed(
        partial(_layer, positions=positions, config=config, mesh=mesh,
                rules=rules, kind=kind), config,
        FLASH_RESIDUALS + ssd_op.RESIDUAL_NAMES) for kind in _KINDS}


def forward_hidden(params, tokens, config: NemotronHConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> (final-norm hidden states [B, S, D], the chosen
    experts of every expert layer [L, B * S, k], in the layers' order)."""
    c = config
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    table = with_logical_constraint(params["embed"], ("vocab", "act_embed"),
                                    mesh=mesh, rules=rules)
    x = llama.embed_rows(table, tokens, mesh).astype(c.dtype)
    x = _residual(x, mesh, rules)
    body = _bodies(c, positions, mesh, rules)

    def pair(x, p):
        x, chosen = body["E"](x, p["experts"])
        return body["M"](x, p["mamba"])[0], chosen

    chosen, done = [], {"pairs": 0, **{k: 0 for k in _KINDS}}
    for seg in c.plan():
        if seg[0] == "pairs":
            first, n = done["pairs"], seg[2]
            done["pairs"] += n
            x, e = jax.lax.scan(pair, x, jax.tree.map(
                lambda a: a[first:first + n], params["pairs"]))
            chosen.append(e)
            device_profiler.count("pattern.periods", n)  # per lowering
        else:
            kind = seg[1]
            x, e = body[kind](x, jax.tree.map(
                lambda a: a[done[kind]], params["one"][_KINDS[kind]]))
            done[kind] += 1
            if e is not None:
                chosen.append(e[None])
            device_profiler.count("pattern.layers_unrolled", 1)
    x = _rms_norm(x, params["final_norm"], c.norm_eps)
    return x, jnp.concatenate(chosen) if chosen else None


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
        emb = llama.embed_rows(params["embed"], next_tokens,
                               mesh).astype(c.dtype)
        x = jnp.concatenate([_rms_norm(emb, p["enorm"], c.norm_eps),
                             _rms_norm(hidden, p["hnorm"], c.norm_eps)],
                            axis=-1) @ p["eh_proj"]
        x = _residual(x, mesh, rules)
        body = _bodies(c, positions, mesh, rules)
        chosen = []
        for j, kind in enumerate(c.mtp_pattern):
            x, e = body[kind](x, p["block"][str(j)])
            if e is not None:
                chosen.append(e)
        device_profiler.count("mtp.depth", 1)  # per lowering
        return _rms_norm(x, p["final_norm"], c.norm_eps), jnp.stack(chosen)


def forward(params, tokens, config: NemotronHConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> next-token logits [B, S, V] float32."""
    x, _ = forward_hidden(params, tokens, config, mesh, rules)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)


def loss_fn(params, batch, config: NemotronHConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """Next-token CE + `mtp_loss_coef` * the MTP block's CE of the token
    after (both through `llama.chunked_ce`, masked by batch["mask"] when
    given). Scalar return (make_train_step contract)."""
    c = config
    inputs, targets, mask = mla_moe._split(batch)
    chunk = c.loss_chunk_size or inputs.shape[1]
    hidden, _ = forward_hidden(params, inputs, c, mesh, rules)
    loss = llama.chunked_ce(hidden, params["lm_head"], targets, mask,
                            chunk=chunk)
    if c.mtp_depth:
        h_mtp, _ = mtp_hidden(params, hidden, targets, c, mesh, rules)
        loss = loss + c.mtp_loss_coef * llama.chunked_ce(
            h_mtp, params["lm_head"], *mla_moe.mtp_targets(targets, mask),
            chunk=chunk)
    return loss


@partial(jax.jit, static_argnames=("config",))
def routing_stats(params, tokens, config: NemotronHConfig):
    """tokens [B, S + 1] -> int32 [expert layers + the MTP block's]: the
    LIVE rows of each expert layer, the (token, choice) pairs whose expert
    is held here. Outside the train step, for tests and chip runs."""
    c = config
    inputs, targets, _ = mla_moe._split({"tokens": tokens})
    hidden, chosen = forward_hidden(params, inputs, c)
    if c.mtp_depth:
        chosen = jnp.concatenate(
            [chosen, mtp_hidden(params, hidden, targets, c)[1]])
    local = chosen - c.first_expert
    return jnp.sum((local >= 0) & (local < c.n_experts_held), axis=(1, 2),
                   dtype=jnp.int32)
