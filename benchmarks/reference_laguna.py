"""Plain reference of Laguna-XS.2 (sliding-window and full attention layers
in a pattern, each kind with its own number of query heads and its own
rotary form, sigmoid-routed experts beside a shared one) and its training
loss: float32 `jax.numpy`, `default_matmul_precision("highest")`, no
kernels, no scan over stacked layers, no sort, no gather of rows, no grouped
matmul; one jitted layer at a time, its attention in blocks of query rows
(dense masks built from positions, a block at a time), so that S 8,192 fits
at the published widths beside a training state.

Follows the published `config.json` (poolside/Laguna-XS.2) and, for what it
leaves open, the configuration's `assumed`. For one row x [S, d], every layer
pre-norm, h = rms(x) (eps 1e-6), by the three per-layer lists:

    Attention, `layer_types[i]`, `heads_per_layer[i]` = H query heads over
    8 KV heads of 128 channels (query head j reads KV head j // (H / 8)):
    q, k, v = h W_q, h W_k, h W_v; RoPE on q and k; scores q . k / sqrt(128);
    query t sees keys j <= t, and in a `sliding_attention` layer only those
    with t - 512 < j; softmax in float32; o_head = softmax v, times
    sigmoid(h w_gate,head) with `attn_gate`; x = x + concat(o) W_o.
    RoPE, sliding layers: theta 10,000 over all 128 channels, channel d
    turning with d + 64. Full layers: theta 500,000 over the FIRST 64
    channels (d with d + 32), the last 64 untouched, at YaRN's frequencies
    (`yarn_inv_freq`, from HF `_compute_yarn_parameters`' formulas), cos and
    sin times `attention_factor`.
    Feed-forward, `mlp_layer_types[i]`: `dense`, SwiGLU of 8,192; `sparse`:
    s = sigmoid(h W_r) over the 256 outputs (softmax with `score`
    "softmax"); chosen = top_8 of s; w = s[chosen] / sum(s[chosen]) x 2.5;
    x = x + sum_j w_j E_j(h) + E_shared(h), with EVERY HELD expert applied to
    every token under the choice's 0/1 matrix.
    Final RMSNorm, untied head, mean CE of t_{i+1}.

The share: `params` holds the experts `first_expert .. + n_experts_held` of
the router's `n_experts`; the choice and the normalisation run over all of
them, the sum over the chosen that are held. What the absent ones would add
is left out, here as in the program.

Departures from the published description: (1) the share above, ids, logits
and loss over a slice of the vocabulary, the layers held (the configuration
says which); (2) every `assumed` of the configuration file: the per-head
output gate, sigmoid scores normalised over the chosen, no QK-norm, SiLU, no
gate on the shared expert; (3) the weights are the program's, cast to
float32, a layer at a time; (4) on a share the combine weights get no
gradient (`reference_joyai.py`, departure 5). Only the parameter layout
(`models/window_moe.py`) is shared with the code under test.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import _rms

FULL, SLIDING = "full_attention", "sliding_attention"
_SHORT = {FULL: "full", SLIDING: "sliding"}
_BLOCK = 512   # query rows a block of attention

_DEFAULTS = {
    "layers": None, "first_expert": 0, "score": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "attn_gate": True,
    "qk_norm": False, "norm_eps": 1e-6, "window": 512,
}


def _get(model, key):
    return model[key] if key in model else _DEFAULTS[key]


def _f(a):
    return a.astype(jnp.float32)


def _swiglu(h, p):
    return (jax.nn.silu(h @ _f(p["w_gate"])) * (h @ _f(p["w_up"]))) \
        @ _f(p["w_down"])


def yarn_bounds(width, base, original, beta_fast, beta_slow):
    """-> (low, high): the pairs between which YaRN's ramp runs."""
    def corr(turns):
        return width * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    return (max(math.floor(corr(beta_fast)), 0),
            min(math.ceil(corr(beta_slow)), width - 1))


def yarn_inv_freq(width, base, factor, original, beta_fast, beta_slow):
    """float64 [width / 2]: f_i = base^(-2i / width), kept below `low`,
    divided by `factor` above `high`, blended linearly between."""
    f = base ** (-np.arange(0, width, 2) / width)
    low, high = yarn_bounds(width, base, original, beta_fast, beta_slow)
    ramp = np.clip((np.arange(width // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    return f * (1 - ramp) + f / factor * ramp


def rope_table(model, layer_type):
    """-> (inv_freq float32 [R / 2], the factor on cos and sin): the rotary
    form `rope_parameters` gives this kind of layer, over R = 128 x
    `partial_rotary_factor` leading channels."""
    g = dict(dict(model["rope_parameters"])[layer_type])
    width = int(model["d_head"] * g.get("partial_rotary_factor", 1))
    if g.get("rope_type", "default") == "yarn":
        freq = yarn_inv_freq(
            width, g["rope_theta"], g["factor"],
            g["original_max_position_embeddings"], g.get("beta_fast", 32),
            g.get("beta_slow", 1))
    else:
        freq = g["rope_theta"] ** (-np.arange(0, width, 2) / width)
    return jnp.asarray(freq, jnp.float32), float(g.get("attention_factor", 1))


def _rope(x, inv_freq, factor):
    """x [S, H, D]: channel d < R / 2 turns with d + R / 2, R = 2 x
    len(inv_freq); the channels from R on pass through."""
    s, r = x.shape[0], 2 * inv_freq.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = factor * jnp.cos(ang)[:, None], factor * jnp.sin(ang)[:, None]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., r:]], -1)


def attend(q, k, v, window):
    """q [S, H, D], k and v [S, G, D] -> [S, H, D]: causal softmax
    attention, within `window` keys where it is not None; a block of query
    rows at a time against all keys under its dense mask."""
    s, h, d = q.shape
    rep = h // k.shape[1]
    rows = _BLOCK if s % _BLOCK == 0 else s
    pos = jnp.arange(s)

    def block(args):
        q_blk, q_pos = args                                 # [rows, H, D]
        keep = q_pos[:, None] >= pos[None]
        if window is not None:
            keep = keep & (q_pos[:, None] - pos[None] < window)
        out = []
        for g in range(k.shape[1]):
            scores = jnp.einsum("qrd,td->rqt", q_blk[:, g * rep:(g + 1) * rep],
                                k[:, g]) / d ** 0.5
            probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
            out.append(jnp.einsum("rqt,td->qrd", probs, v[:, g]))
        return jnp.concatenate(out, 1)

    o = jax.lax.map(block, (q.reshape(s // rows, rows, h, d),
                            pos.reshape(s // rows, rows)))
    return o.reshape(s, h, d)


def attention(x, p, model, layer_type):
    """x [S, d] -> x + gated attention of rms(x), of this kind of layer."""
    eps = _get(model, "norm_eps")
    h = _rms(x, _f(p["attn_norm"]), eps)
    proj = lambda w: jnp.einsum("sd,dhk->shk", h, _f(w))  # noqa: E731
    q, k, v = proj(p["wq"]), proj(p["wk"]), proj(p["wv"])
    if _get(model, "qk_norm"):
        q, k = _rms(q, _f(p["q_norm"]), eps), _rms(k, _f(p["k_norm"]), eps)
    table = rope_table(model, layer_type)
    o = attend(_rope(q, *table), _rope(k, *table), v,
               _get(model, "window") if layer_type == SLIDING else None)
    if _get(model, "attn_gate"):
        o = o * jax.nn.sigmoid(h @ _f(p["w_attn_gate"]))[..., None]
    return x + o.reshape(x.shape[0], -1) @ _f(p["wo"]).reshape(-1, x.shape[1])


def route(h, p, model):
    """h [S, d] -> (dense weights [S, E]: a token's weight for each of ALL
    the router's experts, zero where not chosen; chosen [S, k])."""
    logits = h @ _f(p["router"])
    s = jax.nn.sigmoid(logits) if _get(model, "score") == "sigmoid" \
        else jax.nn.softmax(logits, -1)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(s),
                           model["experts_per_token"])
    chose = jax.nn.one_hot(idx, s.shape[-1])                # [S, k, E] 0/1
    w = jnp.sum(chose * s[:, None], -1)
    if _get(model, "norm_topk_prob"):
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * _get(model, "routed_scaling_factor")
    if model.get("n_experts_held", s.shape[-1]) < s.shape[-1]:
        w = jax.lax.stop_gradient(w)   # departure (4): a share's weights
    return jnp.sum(chose * w[..., None], 1), idx


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


def layer(x, p, model, layer_type, dense: bool):
    """-> (x, chosen [S, k] or None)."""
    x = attention(x, p, model, layer_type)
    h = _rms(x, _f(p["mlp_norm"]), _get(model, "norm_eps"))
    if dense:
        return x + _swiglu(h, p), None
    routed, shared, idx = experts(h, p, model)
    return x + routed + shared, idx


def layer_params(params, model):
    """-> [(published index, that layer's parameters)] in order, out of the
    program's stacks (`models/window_moe.py`: `periods`, the runs of sparse
    sliding layers that end with a full one, all held; `loose` by kind, the
    others)."""
    at = lambda tree, *ix: jax.tree.map(lambda a: a[ix], tree)  # noqa: E731
    types, mlps = list(model["layer_types"]), list(model["mlp_layer_types"])
    held = _get(model, "layers")
    held = list(range(len(types))) if held is None else list(held)
    full = [i for i, t in enumerate(types) if t == FULL]
    period = full[1] - full[0]
    out, have, seen, j = [], set(held), {"periods": 0}, 0
    while j < len(held):
        i = held[j]
        run = range(i, i + period)
        if run[-1] < len(types) and all(
                n in have and mlps[n] == "sparse"
                and types[n] == (FULL if n == run[-1] else SLIDING)
                for n in run):
            for n in range(period - 1):
                out.append((i + n, at(params["periods"]["sliding"],
                                      seen["periods"], n)))
            out.append((run[-1], at(params["periods"]["full"],
                                    seen["periods"])))
            seen["periods"] += 1
            j += period
            continue
        name = f"{_SHORT[types[i]]}_{mlps[i]}"
        out.append((i, at(params["loose"][name], seen.get(name, 0))))
        seen[name] = seen.get(name, 0) + 1
        j += 1
    return out


def _forward(params, tokens, model):
    """tokens [S] -> (logits [S, V], chosen experts per sparse layer)."""
    types, mlps = model["layer_types"], model["mlp_layer_types"]
    run = {(t, d): jax.jit(lambda x, p, t=t, d=d: layer(x, p, model, t, d))
           for t in (FULL, SLIDING) for d in (False, True)}
    with jax.default_matmul_precision("highest"):
        x = _f(params["embed"][tokens])
        chosen = []
        for i, p in layer_params(params, model):
            x, idx = run[types[i], mlps[i] == "dense"](x, p)
            if idx is not None:
                chosen.append(idx)
        h = _rms(x, _f(params["final_norm"]), _get(model, "norm_eps"))
        return h @ _f(params["lm_head"]), chosen


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
