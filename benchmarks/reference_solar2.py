"""Plain reference of Solar-Open2-250B's language model (a gated softmax GQA
layer without rotary embedding, then three KDA linear-attention layers, a
period; sigmoid-routed experts and a shared expert in every layer) and its
training loss: float32 `jax.numpy`, `default_matmul_precision("highest")`,
no kernels, no chunks of the recurrence, no sort, no gather of rows, no
grouped matmul; one jitted layer at a time, and the softmax layer a block of
queries at a time, so that it fits at the published widths and S 8,192.

Follows the published `config.json` (upstage/Solar-Open2-250B) and, for what
it leaves open, the sources the configuration's `assumed` names. For one row
x [S, d], every layer pre-norm, h = rms(x), 64 heads of 128:

    Layer i (published index) mixes with GQA when i % 4 == 0 (`gqa_layers`),
    with KDA otherwise; every layer's second sublayer is the experts.
    KDA: q~, k~, v~ = h W_q, h W_k, h W_v; every channel through a causal
    depthwise conv over time of 4 taps (y_t = sum_j w_j x_{t-3+j}), then
    SiLU; q = l2norm(q) / sqrt(128), k = l2norm(k). a = (h W_f_down) W_f_up
    + dt_bias, g = -exp(A_log_head) x softplus(a) per channel (ANY value
    below 0), alpha = exp(g); beta = 2 x sigmoid(h w_b) (`kda_inputs`). A
    `lax.scan` over TOKENS (`recurrence`), state S [128, 128], S_0 = 0:
        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
    x = x + [rms_head(o_t) * sigmoid((h W_g_down) W_g_up)] W_o. No RoPE.
    GQA: q = h W_q (64 x 128), k, v = h W_k, h W_v (8 x 128), query head j
    reading KV head j // 8; NO rotary embedding; a dense causal mask;
    softmax(q k^T / sqrt(128)) v; x = x + [attn * sigmoid(h W_gate)] W_o, the
    gate a CHANNEL (W_gate [d, 64, 128]; [d, 64]: a head).
    Experts: s = sigmoid(h W_r); chosen = top_8 of s + bias (no gradient),
    one group; w = s[chosen] / sum(s[chosen]) x 1; x = x + sum_j w_j E_j(h)
    + E_shared(h), with EVERY HELD expert applied to every token under the
    routing's mask.
    Final RMSNorm, untied head, CE of t_{i+1}.

The share: `params` holds the experts `first_expert .. + n_experts_held` of
the router's `n_experts`; the choice and the normalisation run over all of
them, the sum over the chosen that are held. What the absent ones would add
is left out, here as in the program.

Departures from the published description: (1) the share above, ids, logits
and loss over a slice of the vocabulary, the layers held (the configuration
says which); (2) every `assumed` of the configuration file: the decay gate's
softplus form, `A_log` a head and `dt_bias` a channel, q and k l2-normalised,
the head-wise RMSNorm before the low-rank output gate, the GQA gate a
channel, the router's sigmoid score and bias in one group; (3) the router
bias's update rule is no part of the loss and is left out; (4) the weights
are the program's, cast to float32, a layer at a time; (5) on a share the
combine weights get no gradient (`reference_joyai.py`, departure 5);
(6) `intermediate_size` 10,240 belongs to no layer (`first_k_dense_replace`
0) and is unused. Only the parameter layout (`models/solar_open2.py`) is
shared with the code under test: the recurrence below is this file's own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import _rms

_DEFAULTS = {
    "n_layers_published": 48, "layers": None, "period": 4, "full_phase": 0,
    "first_expert": 0, "norm_topk_prob": True, "routed_scaling_factor": 1.0,
    "norm_eps": 1e-5, "kda_beta_scale": 2.0,
}
# queries a block of the softmax layer: [64, 256, 8192] float32 scores
_QUERY_BLOCK = 256
# tokens a block of the recurrence under a gradient: 128 states at S 8,192
_TOKEN_BLOCK = 64


def _get(model, key):
    return model[key] if key in model else _DEFAULTS[key]


def _f(a):
    return a.astype(jnp.float32)


def _layers(model):
    held = _get(model, "layers")
    return list(range(_get(model, "n_layers_published"))) if held is None \
        else list(held)


def _is_full(model, i):
    return i % _get(model, "period") == _get(model, "full_phase")


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


def kda_inputs(x, p, model):
    """x [S, d], a KDA layer's input -> (h = rms(x), what the layer's
    recurrence reads: (q, k, v, g [S, H, D], beta [S, H]))."""
    h = _rms(x, _f(p["attn_norm"]), _get(model, "norm_eps"))
    proj = lambda w: jnp.einsum("sd,dhk->shk", h, _f(w))  # noqa: E731
    d = p["wq"].shape[-1]
    q = _l2(_conv_silu(proj(p["wq"]), p["conv_q"])) / d ** 0.5
    k = _l2(_conv_silu(proj(p["wk"]), p["conv_k"]))
    v = _conv_silu(proj(p["wv"]), p["conv_v"])
    g = -jnp.exp(_f(p["a_log"]))[:, None] * jax.nn.softplus(
        _low_rank(h, p, "w_f") + _f(p["dt_bias"]))
    beta = _get(model, "kda_beta_scale") * jax.nn.sigmoid(h @ _f(p["w_b"]))
    return h, (q, k, v, g, beta)


def _low_rank(h, p, name):
    return jnp.einsum("sr,rhk->shk", h @ _f(p[name + "_down"]), _f(p[name]))


def recurrence(q, k, v, g, beta):
    """q, k, v, g [S, H, D], beta [S, H] -> o [S, H, D]: the definition, a
    token at a time, the state [H, D, D] float32 from 0. Its two products
    with the state are written as multiply and sum: float32 through and
    through, with no matmul precision to choose. The tokens are walked in
    blocks under `jax.checkpoint`, so a gradient of this keeps one state a
    block of tokens and not one a token (`benchmarks/train_kda_cell.py`
    takes that gradient at S 8,192)."""
    def token(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        state = jnp.exp(g_t)[..., None] * state                   # [H, D, D]
        read = jnp.sum(state * k_t[..., None], axis=-2)
        state = state + k_t[..., None] * (
            b_t[:, None] * (v_t - read))[:, None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    s, heads, d = q.shape
    block = max(n for n in range(1, _TOKEN_BLOCK + 1) if s % n == 0)
    blocks = lambda a: a.reshape((s // block, block) + a.shape[1:])  # noqa: E731
    _, o = jax.lax.scan(
        jax.checkpoint(lambda state, ts: jax.lax.scan(token, state, ts)),
        jnp.zeros((heads, d, d)), tuple(map(blocks, (q, k, v, g, beta))))
    return o.reshape(v.shape)


def kda(x, p, model):
    """x [S, d] -> x + KDA of rms(x): the recurrence, a token at a time."""
    h, reads = kda_inputs(x, p, model)
    o = _rms(recurrence(*reads), _f(p["o_norm"]), _get(model, "norm_eps")) \
        * jax.nn.sigmoid(_low_rank(h, p, "w_g"))
    return x + o.reshape(x.shape[0], -1) @ _f(p["wo"]).reshape(-1, x.shape[1])


def gqa(x, p, model):
    """x [S, d] -> x + gated softmax attention of rms(x), no positional
    embedding; the queries a block at a time (each block's scores dense
    over all S keys under the causal mask)."""
    eps = _get(model, "norm_eps")
    s = x.shape[0]
    h = _rms(x, _f(p["attn_norm"]), eps)
    q = jnp.einsum("sd,dhk->shk", h, _f(p["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, _f(p["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, _f(p["wv"]))
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    block = min(_QUERY_BLOCK, s)
    while s % block:
        block -= 1

    def attend(first):
        q_b = jax.lax.dynamic_slice_in_dim(q, first, block)
        scores = jnp.einsum("shk,thk->hst", q_b, k) / q.shape[-1] ** 0.5
        seen = (first + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hst,thk->shk", jax.nn.softmax(scores, -1), v)

    attn = jax.lax.map(attend, jnp.arange(0, s, block)).reshape(q.shape)
    w_gate = _f(p["w_attn_gate"])
    gate = jax.nn.sigmoid(jnp.einsum("sd,dhk->shk", h, w_gate)) \
        if w_gate.ndim == 3 else jax.nn.sigmoid(h @ w_gate)[..., None]
    return x + (attn * gate).reshape(s, -1) @ _f(p["wo"]).reshape(
        -1, x.shape[1])


def route(h, p, model):
    """h [S, d] -> (dense weights [S, E]: a token's weight for each of ALL
    the router's experts, zero where not chosen; chosen [S, k])."""
    s = jax.nn.sigmoid(h @ _f(p["router"]))
    biased = jax.lax.stop_gradient(s + _f(p["router_bias"]))
    _, idx = jax.lax.top_k(biased, model["experts_per_token"])
    w = jnp.take_along_axis(s, idx, -1)
    if _get(model, "norm_topk_prob"):
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * _get(model, "routed_scaling_factor")
    if model.get("n_experts_held", s.shape[-1]) < s.shape[-1]:
        w = jax.lax.stop_gradient(w)   # departure (5): a share's weights
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


def layer(x, p, model, full: bool):
    """-> (x, chosen [S, k])."""
    x = gqa(x, p, model) if full else kda(x, p, model)
    h = _rms(x, _f(p["mlp_norm"]), _get(model, "norm_eps"))
    routed, shared, idx = experts(h, p, model)
    return x + routed + shared, idx


def layer_params(params, model):
    """-> [(published index, that layer's parameters)] in order, out of the
    program's stacks (`models/solar_open2.py`: `loose` by kind, the layers
    that fill no whole aligned period here; `periods`, a period its GQA
    layer and then its KDA layers)."""
    at = lambda tree, *ix: jax.tree.map(lambda a: a[ix], tree)  # noqa: E731
    held, period = _layers(model), _get(model, "period")
    out, have = [], set(held)
    seen = {"gqa": 0, "kda": 0, "periods": 0}
    j = 0
    while j < len(held):
        i = held[j]
        if _is_full(model, i) and all(i + n in have for n in range(period)):
            out.append((i, at(params["periods"]["gqa"], seen["periods"])))
            for n in range(period - 1):
                out.append((i + 1 + n, at(params["periods"]["kda"],
                                          seen["periods"], n)))
            seen["periods"] += 1
            j += period - 1
        else:
            kind = "gqa" if _is_full(model, i) else "kda"
            out.append((i, at(params["loose"][kind], seen[kind])))
            seen[kind] += 1
        j += 1
    return out


def _forward(params, tokens, model):
    """tokens [S] -> (logits [S, V], chosen experts per layer)."""
    run = {full: jax.jit(lambda x, p, full=full: layer(x, p, model, full))
           for full in (False, True)}
    with jax.default_matmul_precision("highest"):
        x = _f(params["embed"][tokens])
        chosen = []
        for i, p in layer_params(params, model):
            x, idx = run[_is_full(model, i)](x, p)
            chosen.append(idx)
        h = _rms(x, _f(params["final_norm"]), _get(model, "norm_eps"))
        return h @ _f(params["lm_head"]), chosen


def logits(params, tokens, model):
    """tokens [S] int -> next-token logits [S, vocab] float32."""
    return _forward(params, tokens, model)[0]


def routing(params, inputs, model):
    """rows [R, S] -> chosen experts [layers, R * S, k]."""
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
