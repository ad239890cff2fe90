"""Plain reference of Ling-3.0-flash's language model (KDA linear-attention
and gated MLA layers in a pattern, sigmoid-routed experts chosen within
groups) and its training loss: float32 `jax.numpy`,
`default_matmul_precision("highest")`, no kernels, no chunks, no sort, no
gather of rows, no grouped matmul; one jitted layer at a time so that it
fits at the published widths.

Follows the published `config.json` (inclusionAI/Ling-3.0-flash-VL, the
language model: the tower is not in the catalog's `config`) and, for what it
leaves open, the sources the configuration's `assumed` names. For one row
x [S, d], every layer pre-norm, h = rms(x), per head (32 heads):

    Layer i (published index) mixes with MLA when (i + 1) % 6 == 0, with
    KDA otherwise; i < `first_k_dense_replace` has a dense SwiGLU, the
    others the experts.
    KDA: q~, k~, v~ = h W_q, h W_k, h W_v; every channel through a causal
    depthwise conv over time of 4 taps (y_t = sum_j w_j x_{t-3+j}), then
    SiLU; q = l2norm(q) / sqrt(128), k = l2norm(k). a = h W_f + dt_bias,
    g = -5 x sigmoid(exp(A_log_head) a) per channel, alpha = exp(g);
    beta = sigmoid(h w_b). A `lax.scan` over TOKENS, state S [128, 128],
    S_0 = 0:
        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
    x = x + [rms_head(o_t) * sigmoid(h W_g)] W_o. No RoPE.
    MLA: [q_nope | q_rope] = h W_q per head (128 | 64), no q latent;
    [c_kv | k_rope] = h W_kva (512 | 64), c_kv = rms(c_kv); [k_nope | v] =
    c_kv W_kvb per head; RMSNorm per head on q_nope, q_rope, k_nope and on
    the ONE k_rope, each part by its own statistic; RoPE on q_rope and
    k_rope, channel 2i turning with 2i + 1 (`rope_interleave`, true in the
    language model's own config.json); a dense [S, S] causal mask;
    softmax((q_nope.k_nope + q_rope.k_rope) / sqrt(192)) v;
    x = x + concat_h(attn_h * sigmoid(h w_gate,h)) W_o.
    Experts: s = sigmoid(h W_r); b = s + bias (no gradient). THE GROUP
    LIMIT (`_group_mask`): the experts are 8 contiguous groups of 64; a
    group's score is the sum of its two largest b; the 4 best groups stay;
    chosen = top_8 of b among their 256 experts. w = s[chosen] /
    sum(s[chosen]) x 2.5; x = x + sum_j w_j E_j(h) + E_shared(h), with
    EVERY HELD expert applied to every token under the routing's mask.
    Final RMSNorm, untied head, CE of t_{i+1}.

The share: `params` holds the experts `first_expert .. + n_experts_held` of
the router's `n_experts`; the choice and the normalisation run over all of
them, the sum over the chosen that are held. What the absent ones would add
is left out, here as in the program.

Departures from the published description: (1) the share above, ids,
logits and loss over a slice of the vocabulary, the layers held (the
configuration says which); (2) text only, no MTP block (its depth is not
in the catalog's `config`); (3) every `assumed` of the configuration
file: the gate's form, the output gate per channel, QK-norm read as the
MLA layers' and applied a part at a time, RoPE interleaved as the
language model publishes it, the group score as the sum of the two best; (4) the router bias's update rule is
no part of the loss and is left out; (5) the weights are the program's,
cast to float32, a layer at a time; (6) on a share the combine weights
get no gradient (`reference_joyai.py`, departure 5). Only the parameter
layout (`models/hybrid_moe.py`) is shared with the code under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import _rms

_DEFAULTS = {
    "n_layers_published": 42, "layers": None, "n_dense_layers": 2,
    "period": 6, "conv_size": 4, "kda_lower_bound": -5.0, "first_expert": 0,
    "n_group": 8, "topk_group": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rope_interleave": True, "norm_eps": 1e-6,
}


def _get(model, key):
    return model[key] if key in model else _DEFAULTS[key]


def _f(a):
    return a.astype(jnp.float32)


def _layers(model):
    held = _get(model, "layers")
    return list(range(_get(model, "n_layers_published"))) if held is None \
        else list(held)


def _is_mla(model, i):
    return (i + 1) % _get(model, "period") == 0


def _swiglu(h, p):
    return (jax.nn.silu(h @ _f(p["w_gate"])) * (h @ _f(p["w_up"]))) \
        @ _f(p["w_down"])


def _conv_silu(x, taps):
    """x [S, H, D], taps [K, H, D]: causal, depthwise, then SiLU."""
    k, s = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1,) + x.shape[1:]), x])
    return jax.nn.silu(sum(padded[j:j + s] * _f(taps[j]) for j in range(k)))


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def kda(x, p, model):
    """x [S, d] -> x + KDA of rms(x): the recurrence, a token at a time."""
    eps = _get(model, "norm_eps")
    h = _rms(x, _f(p["attn_norm"]), eps)
    proj = lambda w: jnp.einsum("sd,dhk->shk", h, _f(w))  # noqa: E731
    d = p["wq"].shape[-1]
    q = _l2(_conv_silu(proj(p["wq"]), p["conv_q"])) / d ** 0.5
    k = _l2(_conv_silu(proj(p["wk"]), p["conv_k"]))
    v = _conv_silu(proj(p["wv"]), p["conv_v"])
    a = proj(p["w_f"]) + _f(p["dt_bias"])
    g = _get(model, "kda_lower_bound") * jax.nn.sigmoid(
        jnp.exp(_f(p["a_log"]))[:, None] * a)
    beta = jax.nn.sigmoid(h @ _f(p["w_b"]))                       # [S, H]

    def token(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        state = jnp.exp(g_t)[..., None] * state                   # [H, D, D]
        state = state + b_t[:, None, None] * k_t[..., None] * (
            v_t - jnp.einsum("hkv,hk->hv", state, k_t))[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    heads = q.shape[1]
    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d)), (q, k, v, g, beta))
    o = _rms(o, _f(p["o_norm"]), eps) * jax.nn.sigmoid(proj(p["w_g"]))
    return x + o.reshape(x.shape[0], -1) @ _f(p["wo"]).reshape(-1, x.shape[1])


def _rope(x, theta, interleave):
    """x [S, H, R] -> rotated by position: pair i is channels (2i, 2i + 1)
    with `interleave`, else (i, i + R/2); the channels keep their places."""
    s, _, r = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(r // 2, dtype=jnp.float32) / (r // 2)))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def mla(x, p, model):
    """x [S, d] -> x + gated latent attention of rms(x)."""
    eps, theta = _get(model, "norm_eps"), model["rope_theta"]
    n_h, r_kv = model["n_heads"], model["kv_lora_rank"]
    n_nope = model["qk_nope_head_dim"]
    s = x.shape[0]
    turn = lambda x: _rope(x, theta, _get(model, "rope_interleave"))  # noqa: E731
    h = _rms(x, _f(p["attn_norm"]), eps)
    q = jnp.einsum("sd,dhk->shk", h, _f(p["wq"]))
    kv_a = h @ _f(p["wkv_a"])
    c_kv = _rms(kv_a[:, :r_kv], _f(p["kv_norm"]), eps)
    kv = (c_kv @ _f(p["wkv_b"]).reshape(r_kv, -1)).reshape(s, n_h, -1)
    qn, kn = _f(p["q_head_norm"]), _f(p["k_head_norm"])
    q_nope = _rms(q[..., :n_nope], qn[:n_nope], eps)
    q_rope = turn(_rms(q[..., n_nope:], qn[n_nope:], eps))
    k_nope = _rms(kv[..., :n_nope], kn[:n_nope], eps)
    v = kv[..., n_nope:]
    k_rope = turn(_rms(kv_a[:, None, r_kv:], kn[n_nope:], eps))[:, 0]
    scores = (jnp.einsum("shk,thk->hst", q_nope, k_nope)
              + jnp.einsum("shk,tk->hst", q_rope, k_rope)) \
        / (q.shape[-1] ** 0.5)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    attn = jnp.einsum("hst,thk->shk", jax.nn.softmax(scores, -1), v)
    attn = attn * jax.nn.sigmoid(h @ _f(p["w_attn_gate"]))[..., None]
    return x + attn.reshape(s, -1) @ _f(p["wo"]).reshape(-1, x.shape[1])


def _group_mask(biased, n_group, topk_group):
    """biased [S, E] -> bool [S, E]: the experts of the `topk_group` groups
    whose two largest biased scores add up to most."""
    s, e = biased.shape
    per = e // n_group
    grouped = biased.reshape(s, n_group, per)
    score = jnp.sum(jnp.sort(grouped, -1)[..., -2:], -1)          # [S, G]
    kept = jax.lax.top_k(score, topk_group)[1]
    keep = jnp.sum(jax.nn.one_hot(kept, n_group), 1) > 0          # [S, G]
    return jnp.repeat(keep, per, axis=1)


def route(h, p, model):
    """h [S, d] -> (dense weights [S, E]: a token's weight for each of ALL
    the router's experts, zero where not chosen; chosen [S, k])."""
    s = jax.nn.sigmoid(h @ _f(p["router"]))
    biased = jax.lax.stop_gradient(s + _f(p["router_bias"]))
    n_group = _get(model, "n_group")
    if n_group > 1:
        biased = jnp.where(
            _group_mask(biased, n_group, _get(model, "topk_group")),
            biased, -jnp.inf)
    _, idx = jax.lax.top_k(biased, model["experts_per_token"])
    w = jnp.take_along_axis(s, idx, -1)
    if _get(model, "norm_topk_prob"):
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * _get(model, "routed_scaling_factor")
    if model.get("n_experts_held", s.shape[-1]) < s.shape[-1]:
        w = jax.lax.stop_gradient(w)   # departure (6): a share's weights
    return jnp.sum(jax.nn.one_hot(idx, s.shape[-1]) * w[..., None], 1), idx


def experts(h, p, model):
    """h [S, d] (normed) -> (routed part of the HELD experts [S, d], the
    shared expert's part [S, d], chosen [S, k])."""
    dense_w, idx = route(h, p, model)
    first = _get(model, "first_expert")
    ex = p["experts"]
    routed = jnp.zeros_like(h)
    for e in range(ex["w_gate"].shape[0]):
        routed = routed + dense_w[:, first + e:first + e + 1] * _swiglu(
            h, jax.tree.map(lambda a: a[e], ex))
    return routed, _swiglu(h, p["shared"]), idx


def layer(x, p, model, is_mla: bool, dense: bool):
    """-> (x, chosen [S, k] or None)."""
    x = mla(x, p, model) if is_mla else kda(x, p, model)
    h = _rms(x, _f(p["mlp_norm"]), _get(model, "norm_eps"))
    if dense:
        return x + _swiglu(h, p), None
    routed, shared, idx = experts(h, p, model)
    return x + routed + shared, idx


def layer_params(params, model):
    """-> [(published index, that layer's parameters)] in order, out of the
    program's stacks (`models/hybrid_moe.py`: `dense`; `loose` by kind, the
    expert layers that fill no whole aligned period here; `periods`)."""
    at = lambda tree, *ix: jax.tree.map(lambda a: a[ix], tree)  # noqa: E731
    held, period = _layers(model), _get(model, "period")
    n_dense = _get(model, "n_dense_layers")
    out, have = [], set(held)
    seen = {"dense": 0, "kda": 0, "mla": 0, "periods": 0}
    j = 0
    while j < len(held):
        i = held[j]
        if i < n_dense:
            out.append((i, at(params["dense"], seen["dense"])))
            seen["dense"] += 1
        elif i % period == 0 and all(i + n in have for n in range(period)):
            for n in range(period - 1):
                out.append((i + n, at(params["periods"]["kda"],
                                      seen["periods"], n)))
            out.append((i + period - 1, at(params["periods"]["mla"],
                                           seen["periods"])))
            seen["periods"] += 1
            j += period - 1
        else:
            kind = "mla" if _is_mla(model, i) else "kda"
            out.append((i, at(params["loose"][kind], seen[kind])))
            seen[kind] += 1
        j += 1
    return out


def _forward(params, tokens, model):
    """tokens [S] -> (logits [S, V], chosen experts per expert layer)."""
    n_dense = _get(model, "n_dense_layers")
    run = {(m, d): jax.jit(lambda x, p, m=m, d=d: layer(x, p, model, m, d))
           for m in (False, True) for d in (False, True)}
    with jax.default_matmul_precision("highest"):
        x = _f(params["embed"][tokens])
        chosen = []
        for i, p in layer_params(params, model):
            x, idx = run[_is_mla(model, i), i < n_dense](x, p)
            if idx is not None:
                chosen.append(idx)
        h = _rms(x, _f(params["final_norm"]), _get(model, "norm_eps"))
        return h @ _f(params["lm_head"]), chosen


def logits(params, tokens, model):
    """tokens [S] int -> next-token logits [S, vocab] float32."""
    return _forward(params, tokens, model)[0]


def routing(params, inputs, model):
    """rows [R, S] -> chosen experts [expert layers, R * S, k]."""
    per_row = [_forward(params, i, model)[1] for i in inputs]
    return jnp.stack([jnp.concatenate([row[i] for row in per_row])
                      for i in range(len(per_row[0]))])


def loss_value(params, inputs, targets, model):
    """The training loss over rows [R, S], float32 scalar."""
    nll, count = 0.0, 0
    for row_in, row_t in zip(inputs, targets):
        logp = jax.nn.log_softmax(_forward(params, row_in, model)[0], -1)
        nll = nll - jnp.sum(jnp.take_along_axis(logp, row_t[:, None], -1))
        count += int(row_t.shape[0])
    return nll / count


def loss(params, inputs, targets, model):
    """`loss_value` as a python float (the harness's contract)."""
    return float(loss_value(params, inputs, targets, model))
