"""Plain reference of IBM Granite-4.0-H's language model (`model_type`
`granitemoehybrid` with no experts: every layer a mixer, Mamba-2 or GQA
attention without a rotary embedding, AND a dense gated MLP; every residual
branch, the embedding, the scores and the logits under a published
multiplier; the head tied to the embedding) and its training loss: float32
`jax.numpy`, `default_matmul_precision("highest")`, no kernels, no chunks;
one jitted layer at a time, attention in blocks of `_BLOCK` query rows and
the head in blocks of `_ROWS` positions, so that it fits at the published
widths and S 32,768. Nothing of `ray_tpu` is imported.

As HF `modeling_granitemoehybrid` computes it. For one row of tokens, with
rms(x) = x / sqrt(mean(x^2) + 1e-5) * scale:

    x_0 = embedding_multiplier * E[tokens]                                (12)
    layer i, its kind from `layer_types`:
      x = x + residual_multiplier * mixer_i(rms(x))                     (0.22)
      [g | u] = rms(x) [W_gate | W_up];
      x = x + residual_multiplier * (silu(g) * u) W_down
    logits = rms(x_L) E^T / logits_scaling                                 (8)

    mamba: [z | xBC | dt] = h W_in (4,096 | 4,096 + 2 x 128 | 64), no bias.
    xBC = SiLU(bias + sum_j w_j xBC_{t-3+j}) a channel (causal, 4 taps); x
    [64 heads, 64], B and C [1 group, 128]: every head reads the ONE group.
    Delta = softplus(dt + dt_bias), a = -exp(A_log) Delta a head. A
    `lax.scan` over TOKENS, state H [64, 128] a head, H_0 = 0:
        H_t = exp(a_t) H_{t-1} + Delta_t x_t B_t^T
        y_t = H_t C_t + D x_t
    mixer = [rms_group(y * SiLU(z))] W_out: the gate BEFORE the norm, the
    norm over a group's channels: with one group, over all 4,096.
    attention: q, k, v = h W (32 query heads, 8 KV heads of 64; query head j
    reads KV head j // 4), NO rotary embedding (`position_embedding_type`
    "nope"), softmax(attention_multiplier q k^T + causal) v, W_o: the
    multiplier 1 / 64 is the scale, where 64 ** -0.5 would be 1 / 8.

Departures from the published description: (1) the layers held (the
configuration says which); (2) every `assumed` of the configuration file
(Mamba-2's initialisation constants, no clamp on Delta); (3) the weights are
the program's, cast to float32, a layer at a time; the published fused
[gate | up] projection is two matrices. Only the parameter layout
(`models/granite_hybrid.py`) is shared with the code under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_BLOCK = 512    # query rows of attention at a time
_ROWS = 2048    # positions of the head at a time
_EPS = 1e-5
KINDS = ("mamba", "attention")


def _f(a):
    return a.astype(jnp.float32)


def _rms(x, scale, eps=_EPS):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _eps(model):
    return model.get("norm_eps", _EPS)


def mamba(h, p, model):
    """h [S, d], the layer's normed input -> the Mamba-2 mixer's output
    [S, d]: the recurrence, a token at a time."""
    heads, width = model["mamba_heads"], model["mamba_head_dim"]
    groups, n_state = model["n_groups"], model["state_size"]
    s, wide, gn = h.shape[0], heads * width, groups * n_state
    proj = h @ _f(p["w_in"])
    z = proj[:, :wide]
    xbc = proj[:, wide:2 * wide + 2 * gn]
    dt = proj[:, 2 * wide + 2 * gn:]
    # the causal depthwise conv, tap by tap, then SiLU
    taps = _f(p["conv_w"])
    n_taps = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((n_taps - 1, xbc.shape[1])), xbc])
    conv = _f(p["conv_b"])
    for j in range(n_taps):
        conv = conv + padded[j:j + s] * taps[j]
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :wide].reshape(s, heads, width)
    bs = xbc[:, wide:wide + gn].reshape(s, groups, n_state)
    cs = xbc[:, wide + gn:].reshape(s, groups, n_state)
    delta = jax.nn.softplus(dt + _f(p["dt_bias"]))                # [S, H]
    decay = jnp.exp(-jnp.exp(_f(p["a_log"])) * delta)
    rep = heads // groups

    def token(state, t):
        x_t, b_t, c_t, delta_t, decay_t = t
        b_t, c_t = jnp.repeat(b_t, rep, 0), jnp.repeat(c_t, rep, 0)  # [H, N]
        state = decay_t[:, None, None] * state \
            + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((heads, width, n_state)),
                        (xs, bs, cs, delta, decay))
    y = (y + _f(p["d_skip"])[:, None] * xs).reshape(s, wide)
    # HF's gated norm has a group size of its own: this model's is all of
    # the channels of a group of B and C (one group: all 4,096)
    norm_groups = model.get("gate_norm_groups", groups)
    gated = (y * jax.nn.silu(z)).reshape(s, norm_groups, wide // norm_groups)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + _eps(model))
    return (gated.reshape(s, wide) * _f(p["gate_norm"])) @ _f(p["w_out"])


def attention(h, p, model):
    """h [S, d] -> the attention mixer's output [S, d]: causal GQA, no
    rotary embedding, scores times `attention_multiplier`; a block of query
    rows at a time against all keys under the block's dense mask."""
    s = h.shape[0]
    rep = model["n_heads"] // model["n_kv_heads"]
    q = jnp.einsum("sd,dhk->shk", h, _f(p["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, _f(p["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, _f(p["wv"]))
    rows = _BLOCK if s % _BLOCK == 0 else s
    pos = jnp.arange(s)

    def block(args):
        q_blk, q_pos = args                                   # [rows, H, D]
        keep = q_pos[:, None] >= pos[None]
        out = []
        for g in range(k.shape[1]):
            scores = jnp.einsum("qrd,td->rqt",
                                q_blk[:, g * rep:(g + 1) * rep], k[:, g])
            scores = scores * model["attention_multiplier"]
            probs = jax.nn.softmax(
                jnp.where(keep[None], scores, -jnp.inf), -1)
            out.append(jnp.einsum("rqt,td->qrd", probs, v[:, g]))
        return jnp.concatenate(out, 1)

    o = jax.lax.map(block, (q.reshape((s // rows, rows) + q.shape[1:]),
                            pos.reshape(s // rows, rows)))
    return o.reshape(s, -1) @ _f(p["wo"]).reshape(-1, h.shape[1])


def mlp(h, p):
    """h [S, d] -> (silu(g) * u) W_down, [g | u] = h [W_gate | W_up]."""
    g = h @ _f(p["w_gate"])
    u = h @ _f(p["w_up"])
    return (jax.nn.silu(g) * u) @ _f(p["w_down"])


def layer(x, p, model, kind: str):
    """x [S, d] -> the layer's output: its mixer, then its MLP."""
    eps = _eps(model)
    norm = "norm" if kind == "mamba" else "attn_norm"
    mixer = (mamba if kind == "mamba" else attention)(
        _rms(x, _f(p[norm]), eps), p, model)
    x = x + model["residual_multiplier"] * mixer
    out = mlp(_rms(x, _f(p["mlp_norm"]), eps), p)
    return x + model["residual_multiplier"] * out


def layer_params(params, model):
    """-> [(published index, kind, that layer's parameters)] in order, out
    of the program's stacks (`models/granite_hybrid.py`: whole aligned
    periods under `periods` [period, layer of its kind in the period, ...],
    the other layers under `loose` by kind)."""
    at = lambda tree, *i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
    kinds, period = model["pattern"], model.get("period", 10)
    held = model.get("layers")
    held = list(range(len(kinds))) if held is None else list(held)
    have = set(held)
    out, j = [], 0
    seen = {"periods": 0, "mamba": 0, "attention": 0}
    while j < len(held):
        first = held[j]
        if first % period == 0 and all(
                first + m in have for m in range(period)):
            within = dict.fromkeys(KINDS, 0)
            for m in range(period):
                kind = kinds[first + m]
                out.append((first + m, kind, at(
                    params["periods"][kind], seen["periods"], within[kind])))
                within[kind] += 1
            seen["periods"] += 1
            j += period
        else:
            kind = kinds[first]
            out.append((first, kind, at(params["loose"][kind], seen[kind])))
            seen[kind] += 1
            j += 1
    return out


def hidden(params, tokens, model):
    """tokens [S] -> the final norm's output [S, d]."""
    run = {kind: jax.jit(lambda x, p, kind=kind: layer(x, p, model, kind))
           for kind in KINDS}
    x = _f(params["embed"][tokens])
    x = model["embedding_multiplier"] * x
    for _, kind, p in layer_params(params, model):
        x = run[kind](x, p)
    return _rms(x, _f(params["final_norm"]), _eps(model))


def logits(params, tokens, model):
    """tokens [S] int -> next-token logits [S, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, model)
        tied = _f(params["embed"]).T          # the head IS the embedding
        return (h @ tied) / model["logits_scaling"]


def loss_value(params, inputs, targets, model):
    """The training loss over rows [R, S], float32 scalar (differentiable):
    mean CE of t_{i+1}; the tied head, the division by `logits_scaling` and
    the log-softmax in blocks of `_ROWS` positions."""
    nll, count = 0.0, 0
    with jax.default_matmul_precision("highest"):
        tied = _f(params["embed"]).T          # the head IS the embedding
        for row_in, row_t in zip(inputs, targets):
            h = hidden(params, row_in, model)
            for at in range(0, h.shape[0], _ROWS):
                lg = h[at:at + _ROWS] @ tied
                lg = lg / model["logits_scaling"]
                logp = jax.nn.log_softmax(lg, -1)
                nll = nll - jnp.sum(jnp.take_along_axis(
                    logp, row_t[at:at + _ROWS, None], -1))
            count += int(row_t.shape[0])
    return nll / count


def loss(params, inputs, targets, model):
    """`loss_value` as a python float (the harness's contract)."""
    return float(loss_value(params, inputs, targets, model))
