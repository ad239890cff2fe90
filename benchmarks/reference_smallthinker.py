"""Plain reference of SmallThinker-21BA3B-Instruct (sliding-window layers
under RoPE among full layers with NO positional embedding, a router that
reads the ATTENTION's input, softmax-routed ReLU-gated experts and no shared
one) and its training loss: float32 `jax.numpy`,
`default_matmul_precision("highest")`, no kernels, no scan over stacked
layers, no sort, no gather of rows, no grouped matmul; one jitted layer at a
time, its attention in blocks of query rows (dense masks built from
positions, a block at a time: `reference_laguna.attend`, itself plain), the
loss in blocks of rows, so that S 16,384 fits at the published widths beside
a training state.

Follows the published `config.json` (PowerInfer/SmallThinker-21BA3B-Instruct)
and, for what it leaves open, the configuration's `assumed`. For one row
x [S, d], layer i, every norm an RMSNorm (eps 1e-6):

    h = rms(x; g_in).
    Router, BEFORE attention: r = h W_r over the 64 outputs; chosen = the 6
    largest logits; w = softmax over THOSE 6 logits
    (`moe_primary_router_apply_softmax`; `norm_topk_prob` then divides by a
    sum that is 1). `router_input` says what r is formed from:
    "attention_input" h (ASSUMED), "residual" x itself, "ffn_input" u below
    (as every other model of this repo).
    Attention on h: 28 query heads over 4 KV heads of 128 channels (query
    head j reads KV head j // 7): q, k, v = h W_q, h W_k, h W_v; scores
    q . k / sqrt(128); softmax in float32; y = x + concat(o) W_o.
    `sliding_window_layout[i]` 1: query t sees keys t - 4096 < j <= t; 0:
    every key j <= t. `rope_layout[i]` 1: RoPE on q and k at theta 1,500,000
    over the whole head, channel d turning with d + 64; 0: none. The two
    lists are read apart, each for what it says.
    u = rms(y; g_post); x' = y + sum over the chosen e of
    w_e W_down,e (relu(W_gate,e u) * (W_up,e u)), with EVERY HELD expert
    applied to every token under the choice's 0/1 matrix (`expert_form`
    "swiglu": silu for relu, the alternative reading). No shared expert.
    After the last layer: RMSNorm, untied head, mean CE of t_{i+1}.

The share: `params` holds the experts `first_expert .. + n_experts_held` of
the router's `n_experts`; the choice and the softmax run over all of them,
the sum over the chosen that are held. What the absent ones would add is
left out, here as in the program.

Departures from the published description: (1) the share above, ids, logits
and loss over a slice of the vocabulary, the layers held (the configuration
says which); (2) every `assumed` of the configuration file: what the router
reads, the window's convention, ReLU, no bias, QK-norm or gate, no auxiliary
loss, nothing "secondary"; (3) the weights are the program's, cast to
float32, a layer at a time; (4) on a share the combine weights get no
gradient (`reference_joyai.py`, departure 5). Only the parameter layout
(`models/window_moe.py`) is shared with the code under test; nothing of
`ray_tpu` is imported.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import _rms
from benchmarks.reference_laguna import FULL, SLIDING, _rope, attend
from benchmarks.reference_laguna import layer_params as _laguna_layer_params

_ROWS = 2048   # rows a block of the loss

_DEFAULTS = {
    "layers": None, "first_expert": 0, "norm_eps": 1e-6, "window": 4096,
    "router_input": "attention_input", "expert_form": "reglu",
    "rope_layout": None,
}


def _get(model, key):
    return model[key] if key in model else _DEFAULTS[key]


def _f(a):
    return a.astype(jnp.float32)


def layer_kinds(model):
    """-> [(sliding, rope)] a published layer: whether its attention is
    within the window, whether q and k turn; `layer_types` as names or as
    the published 0 / 1 of `sliding_window_layout`."""
    sliding = [t in (1, SLIDING) for t in model["layer_types"]]
    rope = _get(model, "rope_layout")
    return list(zip(sliding, [True] * len(sliding) if rope is None
                    else map(bool, rope)))


def layer_params(params, model):
    """`reference_laguna.layer_params` (the program's stacks, read a
    published layer at a time) under the kinds' names."""
    names = dict(model, layer_types=[
        SLIDING if s else FULL for s, _ in layer_kinds(model)])
    return _laguna_layer_params(params, names)


def rope_freq(model, sliding: bool):
    """float32 [64]: theta ** (-2i / 128), the kind's theta."""
    group = dict(dict(model["rope_parameters"])[SLIDING if sliding else FULL])
    width = model["d_head"]
    return jnp.asarray(
        float(group["rope_theta"]) ** (-jnp.arange(0, width, 2) / width),
        jnp.float32)


def route(r_in, p, model):
    """r_in [S, d], what the router reads -> (dense weights [S, E]: a
    token's weight for each of ALL the router's experts, zero where not
    chosen; chosen [S, k]): the k largest LOGITS, the softmax over them."""
    logits = r_in @ _f(p["router"])
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(logits),
                           model["experts_per_token"])
    chose = jax.nn.one_hot(idx, logits.shape[-1])            # [S, k, E] 0/1
    w = jax.nn.softmax(jnp.sum(chose * logits[:, None], -1), -1)
    if model.get("n_experts_held", logits.shape[-1]) < logits.shape[-1]:
        w = jax.lax.stop_gradient(w)   # departure (4): a share's weights
    return jnp.sum(chose * w[..., None], 1), idx


def experts(u, dense_w, p, model):
    """u [S, d] (normed) -> the routed part of the HELD experts [S, d]."""
    gate = jax.nn.relu if _get(model, "expert_form") == "reglu" \
        else jax.nn.silu
    first = _get(model, "first_expert")
    ex = p["experts"]
    routed = jnp.zeros_like(u)
    for e in range(ex["w_gate"].shape[0]):
        hidden = gate(u @ _f(ex["w_gate"][e])) * (u @ _f(ex["w_up"][e]))
        routed = routed + dense_w[:, first + e:first + e + 1] * (
            hidden @ _f(ex["w_down"][e]))
    return routed


def layer(x, p, model, sliding: bool, rope: bool):
    """x [S, d] -> (x', chosen [S, k])."""
    eps, reads = _get(model, "norm_eps"), _get(model, "router_input")
    h = _rms(x, _f(p["attn_norm"]), eps)
    ahead = {"attention_input": h, "residual": x}.get(reads)
    if ahead is not None:   # the router before the attention
        dense_w, idx = route(ahead, p, model)
    proj = lambda w: jnp.einsum("sd,dhk->shk", h, _f(w))  # noqa: E731
    q, k, v = proj(p["wq"]), proj(p["wk"]), proj(p["wv"])
    if rope:
        freq = rope_freq(model, sliding)
        q, k = _rope(q, freq, 1.0), _rope(k, freq, 1.0)
    o = attend(q, k, v, _get(model, "window") if sliding else None)
    y = x + o.reshape(x.shape[0], -1) @ _f(p["wo"]).reshape(-1, x.shape[1])
    u = _rms(y, _f(p["mlp_norm"]), eps)
    if ahead is None:
        if reads != "ffn_input":
            raise ValueError(f"router_input {reads!r}")
        dense_w, idx = route(u, p, model)
    return y + experts(u, dense_w, p, model), idx


def hidden(params, tokens, model, kinds=None):
    """tokens [S] -> (final-norm hidden states [S, d], the chosen experts a
    layer). `kinds`: `layer_kinds`' answer overridden (a control that runs a
    layer as the other kind, `tools/smallthinker_chip_check.py`)."""
    kinds = kinds or layer_kinds(model)
    run = {kind: jax.jit(lambda x, p, kind=kind: layer(x, p, model, *kind))
           for kind in set(kinds)}
    x = _f(params["embed"][tokens])
    chosen = []
    for i, p in layer_params(params, model):
        x, idx = run[kinds[i]](x, p)
        chosen.append(idx)
    return _rms(x, _f(params["final_norm"]), _get(model, "norm_eps")), chosen


def _forward(params, tokens, model):
    """tokens [S] -> (logits [S, V], the chosen experts a layer)."""
    with jax.default_matmul_precision("highest"):
        h, chosen = hidden(params, tokens, model)
        return h @ _f(params["lm_head"]), chosen


def loss_value(params, inputs, targets, model):
    """The training loss over rows [R, S], float32 scalar; the head and the
    log-softmax in blocks of `_ROWS` positions."""
    nll, count = 0.0, 0
    with jax.default_matmul_precision("highest"):
        head = _f(params["lm_head"])
        for row_in, row_t in zip(inputs, targets):
            h, _ = hidden(params, row_in, model)
            for at in range(0, h.shape[0], _ROWS):
                logp = jax.nn.log_softmax(h[at:at + _ROWS] @ head, -1)
                nll = nll - jnp.sum(jnp.take_along_axis(
                    logp, row_t[at:at + _ROWS, None], -1))
            count += int(row_t.shape[0])
    return nll / count


def loss(params, inputs, targets, model):
    """`loss_value` as a python float (the harness's contract)."""
    return float(loss_value(params, inputs, targets, model))
