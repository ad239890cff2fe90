"""Plain reference of NVIDIA-Nemotron-3-Super-120B-A12B's language model
(`model_type` `nemotron_h`: Mamba-2 state-space layers, experts in a latent,
GQA attention without a rotary embedding, each layer ONE sublayer; an MTP
block) and its training loss: float32 `jax.numpy`,
`default_matmul_precision("highest")`, no kernels, no chunks, no sort, no
gather of rows, no grouped matmul; one jitted layer at a time so that it
fits at the published widths.

Follows the published `config.json` and, for what it leaves open, the
sources the configuration's `assumed` names. For one row x [S, d], every
layer x = x + f(h), h = rms(x) (eps 1e-5), its kind from the published
pattern (`M`, `E`, `*`):

    M: [z | xBC | dt] = h W_in (8,192 | 8,192 + 2 x 8 x 128 | 128). xBC =
    SiLU(bias + sum_j w_j xBC_{t-3+j}) a channel (causal, 4 taps); x
    [128 heads, 64], B and C [8 groups, 128], head j reading group j // 16.
    Delta = softplus(dt + dt_bias), a = -exp(A_log) Delta a head. A
    `lax.scan` over TOKENS, state H [64, 128] a head, H_0 = 0:
        H_t = exp(a_t) H_{t-1} + Delta_t x_t B_t^T
        y_t = H_t C_t + D x_t
    f = [rms_group(y * SiLU(z))] W_out: the gate BEFORE the norm, the norm
    over each group's 1,024 channels, one scale [8,192].
    *: q, k, v = h W (32 query heads, 2 KV heads of 128; query head j reads
    KV head j // 16), NO rotary embedding, a dense [S, S] causal mask,
    softmax(q k^T / sqrt(128)) v, W_o.
    E: s = sigmoid(h W_r); chosen = top_22 of s + bias (no gradient);
    w = s[chosen] / sum(s[chosen]) x 5. u = h W_down (4,096 -> 1,024);
    f = [sum_j w_j W2_j relu(W1_j u)^2] W_up + W2_s relu(W1_s h)^2, with
    EVERY HELD expert applied to every token under the routing's mask.
    Final RMSNorm, untied head, CE of t_{i+1}.
    MTP: x = [rms(Emb(t_{i+1})) | rms(h_i)] W_eh (h_i the main model's
    final-norm output), a `*` layer, an `E` layer, its own final norm, the
    shared head, CE of t_{i+2}; loss = CE + 0.1 CE_mtp.

The share: `params` holds the experts `first_expert .. + n_experts_held` of
the router's `n_experts`; the choice and the normalisation run over all of
them, the sum over the chosen that are held. What the absent ones would add
is left out, here as in the program.

Departures from the published description: (1) the share above, ids, logits
and loss over a slice of the vocabulary, the layers held (the configuration
says which); (2) every `assumed` of the configuration file: no rotary
embedding, one pair of latent projections a layer and no norm between, the
MTP block's form and its loss weight, no clamp on Delta; (3) the router
bias's update rule is no part of the loss and is left out; (4) the weights
are the program's, cast to float32, a layer at a time; (5) on a share the
combine weights get no gradient (`reference_joyai.py`, departure 5). Only
the parameter layout (`models/nemotron_h.py`) is shared with the code
under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import _rms

_DEFAULTS = {
    "layers": None, "conv_size": 4, "first_expert": 0, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 5.0,
    "norm_eps": 1e-5, "mtp_depth": 1, "mtp_pattern": "*E",
    "mtp_loss_coef": 0.1,
}
_NAMES = {"M": "mamba", "E": "experts", "*": "attn"}


def _get(model, key):
    return model[key] if key in model else _DEFAULTS[key]


def _f(a):
    return a.astype(jnp.float32)


def _layers(model):
    held = _get(model, "layers")
    return list(range(len(model["pattern"]))) if held is None else list(held)


def mamba(x, p, model):
    """x [S, d] -> x + Mamba-2 of rms(x): the recurrence, a token at a
    time."""
    eps = _get(model, "norm_eps")
    heads, width = model["mamba_heads"], model["mamba_head_dim"]
    groups, n_state = model["n_groups"], model["state_size"]
    s, wide, gn = x.shape[0], heads * width, groups * n_state
    proj = _rms(x, _f(p["norm"]), eps) @ _f(p["w_in"])
    z, xbc, dt = (proj[:, :wide], proj[:, wide:2 * wide + 2 * gn],
                  proj[:, 2 * wide + 2 * gn:])
    taps = _f(p["conv_w"])
    padded = jnp.concatenate([jnp.zeros((taps.shape[0] - 1, xbc.shape[1])),
                              xbc])
    xbc = jax.nn.silu(_f(p["conv_b"]) + sum(
        padded[j:j + s] * taps[j] for j in range(taps.shape[0])))
    per_head = lambda v: jnp.repeat(  # noqa: E731
        v.reshape(s, groups, n_state), heads // groups, axis=1)
    xs = xbc[:, :wide].reshape(s, heads, width)
    bs, cs = per_head(xbc[:, wide:wide + gn]), per_head(xbc[:, wide + gn:])
    delta = jax.nn.softplus(dt + _f(p["dt_bias"]))                # [S, H]
    decay = jnp.exp(-jnp.exp(_f(p["a_log"])) * delta)

    def token(state, t):
        x_t, b_t, c_t, delta_t, decay_t = t
        state = decay_t[:, None, None] * state \
            + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((heads, width, n_state)),
                        (xs, bs, cs, delta, decay))
    y = (y + _f(p["d_skip"])[:, None] * xs).reshape(s, wide)
    gated = (y * jax.nn.silu(z)).reshape(s, groups, wide // groups)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + eps)
    return x + (gated.reshape(s, wide) * _f(p["gate_norm"])) @ _f(p["w_out"])


def attention(x, p, model):
    """x [S, d] -> x + causal GQA of rms(x), no rotary embedding."""
    s = x.shape[0]
    rep = model["n_heads"] // model["n_kv_heads"]
    h = _rms(x, _f(p["attn_norm"]), _get(model, "norm_eps"))
    q = jnp.einsum("sd,dhk->shk", h, _f(p["wq"]))
    k = jnp.repeat(jnp.einsum("sd,dhk->shk", h, _f(p["wk"])), rep, axis=1)
    v = jnp.repeat(jnp.einsum("sd,dhk->shk", h, _f(p["wv"])), rep, axis=1)
    scores = jnp.einsum("shk,thk->hst", q, k) / (q.shape[-1] ** 0.5)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    attn = jnp.einsum("hst,thk->shk", jax.nn.softmax(scores, -1), v)
    return x + attn.reshape(s, -1) @ _f(p["wo"]).reshape(-1, x.shape[1])


def route(h, p, model):
    """h [S, d] -> (dense weights [S, E]: a token's weight for each of ALL
    the router's experts, zero where not chosen; chosen [S, k])."""
    s = jax.nn.sigmoid(h @ _f(p["router"]))
    if _get(model, "n_group") != 1:
        raise NotImplementedError("this model chooses among all experts")
    biased = jax.lax.stop_gradient(s + _f(p["router_bias"]))
    _, idx = jax.lax.top_k(biased, model["experts_per_token"])
    w = jnp.take_along_axis(s, idx, -1)
    if _get(model, "norm_topk_prob"):
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * _get(model, "routed_scaling_factor")
    if model.get("n_experts_held", s.shape[-1]) < s.shape[-1]:
        w = jax.lax.stop_gradient(w)   # departure (5): a share's weights
    return jnp.sum(jax.nn.one_hot(idx, s.shape[-1]) * w[..., None], 1), idx


def _relu2(h, p):
    return jnp.square(jax.nn.relu(h @ _f(p["w_up"]))) @ _f(p["w_down"])


def experts(x, p, model):
    """x [S, d] -> (x + the HELD experts' part through the latent + the
    shared expert's, of rms(x); chosen [S, k])."""
    h = _rms(x, _f(p["mlp_norm"]), _get(model, "norm_eps"))
    dense_w, idx = route(h, p, model)
    first = _get(model, "first_expert")
    latent = h @ _f(p["w_latent_in"])
    routed = jnp.zeros_like(latent)
    for e in range(p["experts"]["w_up"].shape[0]):
        routed = routed + dense_w[:, first + e:first + e + 1] * _relu2(
            latent, jax.tree.map(lambda a: a[e], p["experts"]))
    return x + routed @ _f(p["w_latent_out"]) + _relu2(h, p["shared"]), idx


def layer(x, p, model, kind: str):
    """-> (x, chosen [S, k] or None)."""
    if kind == "E":
        return experts(x, p, model)
    return (mamba if kind == "M" else attention)(x, p, model), None


def layer_params(params, model):
    """-> [(published index, kind, that layer's parameters)] in order, out
    of the program's stacks (`models/nemotron_h.py`: two or more consecutive
    (E, M) pairs under `pairs`, the other layers under `one` by kind)."""
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
    held, kinds = _layers(model), model["pattern"]
    seen = {"pairs": 0, "M": 0, "E": 0, "*": 0}
    out, j = [], 0
    while j < len(held):
        i, n = held[j], 0
        while held[j + 2 * n:j + 2 * n + 2] == [i + 2 * n, i + 2 * n + 1] \
                and kinds[i + 2 * n:i + 2 * n + 2] == "EM":
            n += 1
        if n >= 2:
            for m in range(n):
                pair = at(params["pairs"], seen["pairs"])
                out += [(i + 2 * m, "E", pair["experts"]),
                        (i + 2 * m + 1, "M", pair["mamba"])]
                seen["pairs"] += 1
            j += 2 * n
        else:
            kind = kinds[i]
            out.append((i, kind, at(params["one"][_NAMES[kind]], seen[kind])))
            seen[kind] += 1
            j += 1
    return out


def _forward(params, tokens, next_tokens, model):
    """tokens [S], next_tokens [S] or None -> (logits [S, V], MTP logits
    [S, V] or None, chosen experts per expert layer, the MTP block's
    last)."""
    eps = _get(model, "norm_eps")
    run = {kind: jax.jit(lambda x, p, kind=kind: layer(x, p, model, kind))
           for kind in _NAMES}
    with jax.default_matmul_precision("highest"):
        x = _f(params["embed"][tokens])
        chosen = []
        for _, kind, p in layer_params(params, model):
            x, idx = run[kind](x, p)
            if idx is not None:
                chosen.append(idx)
        h = _rms(x, _f(params["final_norm"]), eps)
        head = _f(params["lm_head"])
        mtp_logits = None
        if _get(model, "mtp_depth") and next_tokens is not None:
            m = params["mtp"]
            x = jnp.concatenate(
                [_rms(_f(params["embed"][next_tokens]), _f(m["enorm"]), eps),
                 _rms(h, _f(m["hnorm"]), eps)], -1) @ _f(m["eh_proj"])
            for j, kind in enumerate(_get(model, "mtp_pattern")):
                x, idx = run[kind](x, m["block"][str(j)])
                if idx is not None:
                    chosen.append(idx)
            mtp_logits = _rms(x, _f(m["final_norm"]), eps) @ head
        return h @ head, mtp_logits, chosen


def logits(params, tokens, model):
    """tokens [S] int -> next-token logits [S, vocab] float32."""
    return _forward(params, tokens, None, model)[0]


def routing(params, inputs, targets, model):
    """rows [R, S] -> chosen experts [expert layers (+ MTP), R * S, k]."""
    per_row = [_forward(params, i, t, model)[2]
               for i, t in zip(inputs, targets)]
    return jnp.stack([jnp.concatenate([row[i] for row in per_row])
                      for i in range(len(per_row[0]))])


def loss_terms(params, inputs, targets, model):
    """rows [R, S] -> (mean CE of t_{i+1}, mean CE_mtp of t_{i+2} over the
    positions that have one), float32 scalars (differentiable)."""
    nll = nll_mtp = 0.0
    count = count_mtp = 0
    for row_in, row_t in zip(inputs, targets):
        lg, lg_mtp, _ = _forward(params, row_in, row_t, model)
        logp = jax.nn.log_softmax(lg, -1)
        nll = nll - jnp.sum(jnp.take_along_axis(logp, row_t[:, None], -1))
        count += int(row_t.shape[0])
        if lg_mtp is not None:
            logp = jax.nn.log_softmax(lg_mtp[:-1], -1)
            nll_mtp = nll_mtp - jnp.sum(
                jnp.take_along_axis(logp, row_t[1:, None], -1))
            count_mtp += int(row_t.shape[0]) - 1
    return nll / count, nll_mtp / max(count_mtp, 1)


def loss_value(params, inputs, targets, model):
    """The training loss over rows [R, S], float32 scalar."""
    ce, ce_mtp = loss_terms(params, inputs, targets, model)
    return ce + _get(model, "mtp_loss_coef") * ce_mtp \
        if _get(model, "mtp_depth") else ce


def loss(params, inputs, targets, model):
    """`loss_value` as a python float (the harness's contract)."""
    return float(loss_value(params, inputs, targets, model))
