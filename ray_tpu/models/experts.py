"""The routed-experts block several model modules share: a router, the
experts this program holds and, where the layer has one, a shared expert
every token passes, through `parallel/moe.py` in ONE program: all
`n_experts`, or one chip's share of an expert-parallel deployment run
without its exchange (`Share`: the router keeps all its outputs, the pairs
whose expert is absent are left out)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import blocks
from ray_tpu.parallel import moe
from ray_tpu.parallel.sharding import LogicalAxisRules


class Share:
    """For a config with `n_experts` (the router's outputs), `n_experts_held`
    (None: all) and `first_expert`: which of them this program holds."""

    @property
    def held(self):
        """`moe_layer`'s `held`: None where every expert is here."""
        if self.n_experts_held in (None, self.n_experts):
            return None
        if not 0 <= self.first_expert \
                <= self.n_experts - self.n_experts_held:
            raise ValueError("held experts outside the router's outputs")
        return self.first_expert, self.n_experts_held


def routed_axes(L):
    """The router, the held experts and the shared expert of a layer."""
    # the held experts' dim is NOT the `ep` axis's: a share has no exchange
    return {
        "router": L + ("embed", None), "router_bias": L + (None,),
        "experts": blocks.ffn_axes(L + (None,)),
        "shared": blocks.ffn_axes(L),
    }


def init_routed(config, k_r, k_b, ks):
    """A layer's router (0.02 normal), its bias (float32 N(0, 0.01^2): not
    zero, so that it changes choices wherever two scores lie that close),
    the held experts and the shared one."""
    c = config
    return {
        "router": (jax.random.normal(k_r, (c.d_model, c.n_experts))
                   * 0.02).astype(c.dtype),
        "router_bias": jax.random.normal(k_b, (c.n_experts,)) * 0.01,
        "experts": blocks.init_ffn(c, ks[:3], (c.n_experts_held,),
                                   c.d_ff_expert),
        "shared": blocks.init_ffn(c, ks[3:], (),
                                  c.n_shared_experts * c.d_ff_expert),
    }


def routing(h, p, config):
    """h [T, D], what this model's router reads -> `moe.route`'s choice at
    the config's `score`; a layer without a `router_bias` chooses on the
    scores alone."""
    c = config
    return moe.route(h, p["router"], c.experts_per_token, c.norm_topk_prob,
                     score=c.score, bias=p.get("router_bias"),
                     scale=c.routed_scaling_factor, n_group=c.n_group,
                     topk_group=c.topk_group)


def expert_parts(h, p, config, mesh=None, ahead=None, form: str = "swiglu"):
    """The routed block of h [B, S, D], a layer's normed input, before the
    residual is added -> (the held experts' part [B, S, D], the shared
    expert's [B, S, D] or None where the layer has none, the chosen experts
    [B * S, k]): two parts, because `expert_sublayer` adds them to x one
    after the other. The choice is `routing` of h, or `ahead`, the same
    formed EARLIER from what the model's router reads instead
    (`models/window_moe.py`: the attention's input); the experts are of
    `form` (`moe_layer`), beside a shared SwiGLU every token passes where
    the layer has one (`p["shared"]`)."""
    c = config
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        raise NotImplementedError(
            "these experts run in one program (all of them, or one chip's "
            "share without the exchange): no `ep` mesh axis")
    b, s, d = h.shape
    rows = h.reshape(b * s, d)
    if ahead is None:
        ahead = routing(rows, p, c)
    routed, aux = moe.moe_layer(rows, None, p["experts"], c.experts_per_token,
                                held=c.held, form=form, routing=ahead)
    if "shared" not in p:
        return routed.reshape(b, s, d), None, aux.experts
    with jax.named_scope("moe.shared"):
        sh = p["shared"]
        shared = (jax.nn.silu(h @ sh["w_gate"]) * (h @ sh["w_up"])) \
            @ sh["w_down"]
    return routed.reshape(b, s, d), shared, aux.experts


def expert_sublayer(x, p, config, mesh=None,
                    rules: Optional[LogicalAxisRules] = None, ahead=None,
                    form: str = "swiglu"):
    """x [B, S, D] -> (x + routed + shared experts of RMSNorm(x), the
    chosen experts [B * S, k]) (`expert_parts`)."""
    h = blocks.rms_norm(x, p["mlp_norm"], config.norm_eps)
    routed, shared, chosen = expert_parts(h, p, config, mesh, ahead, form)
    x = x + routed
    if shared is not None:
        x = x + shared
    return blocks.residual(x, mesh, rules), chosen


def live_rows(chosen, config):
    """chosen [L, T, k], the experts every (token, choice) pair of L routed
    blocks goes to -> int32 [L]: each block's LIVE rows, the pairs whose
    expert is held here."""
    first, n_held = config.held or (0, config.n_experts)
    local = chosen - first
    return jnp.sum((local >= 0) & (local < n_held), axis=(1, 2),
                   dtype=jnp.int32)


def capacity_loads(live, rows: int, config):
    """`live_rows`' counts over the rows of the capacity a block of `rows`
    tokens runs at (`moe.capacity_load`): what of its buffer the row moves
    visit. float32, as `live`."""
    _, n_held = config.held or (0, config.n_experts)
    return moe.capacity_load(live, moe.share_capacities(
        rows, config.experts_per_token, n_held, config.n_experts))
