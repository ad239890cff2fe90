"""Plain reference of the Xing4.0 decoder and its training loss: the
DeepSeek-V3 layer (MLA, sigmoid-routed experts, a shared expert, an MTP
block) on a residual path of FOUR streams mixed by learned,
Sinkhorn-normalised maps (mHC, arXiv:2512.24880, over Hyper-Connections,
arXiv:2409.19606). float32 `jax.numpy`, `default_matmul_precision(
"highest")`, no kernels, no sort, no gather of rows, no grouped matmul; one
jitted layer at a time so that it fits at the published widths.

Follows the published `config.json` (XingChen-AGI/Xing4.0-29B-A4B,
`model_type` xing4_0: `hc_mult` 4, `hc_sinkhorn_iters` 20, `hc_eps` 1e-6,
`mhc_h_res_clamp_min/max` -30 / 30, `rope_scaling` yarn) and, for what a
config cannot say, the two papers and HF `modeling_deepseek_v3.py`. For one
row of S tokens the state is X [S, n, C], n = 4. One CONNECTION around a
sublayer F, a token at a time:

    xbar   = vec(X) / rms(vec(X))                     over the n C channels
    a      = xbar . phi                               phi [n C, n + n + n^2]
    H_pre  = sigmoid(alpha_pre a_pre + b_pre)         [n]
    H_post = 2 sigmoid(alpha_post a_post + b_post)    [n]
    M      = exp(clip(alpha_res mat(a_res) + b_res, -30, 30))      [n, n]
    20 times: M = M / (rowsum(M) + hc_eps); M = M / (colsum(M) + hc_eps)
    h      = sum_i H_pre[i] X[i]
    y      = F(h)
    X'[i]  = sum_j M[i, j] X[j] + H_post[i] y

A layer is two connections: F = MLA(rms(h)), then F = SwiGLU(rms(h)) (the
leading dense layers) or routed + shared experts of rms(h). X_0[i] = Emb(t)
for every i; the final hidden state is rms(sum_i X_L[i]). The MTP block
takes that h_i as DeepSeek-V3's does, [rms_e(Emb(t_{i+1})) | rms_h(h_i)]
W_eh, copies it into its own four streams, runs one expert layer of two
connections, sums the streams and applies its own final norm; the shared
embedding and head: CE_mtp of t_{i+2}. loss = CE + mtp_loss_coef * CE_mtp.

MLA is DeepSeek-V3's (`reference_joyai.py` says it line by line) with YaRN on
the 64 rotary channels: pair i turns at theta^(-2i/64), divided by `factor`
where it turns less than `beta_slow` times over the original context, kept
where it turns more than `beta_fast` times, a linear ramp between; cos and
sin times mscale(`mscale`) / mscale(`mscale_all_dim`) (1 here); scores times
192^-0.5 mscale(`mscale_all_dim`)^2, mscale(m) = 0.1 m ln(factor) + 1.
`rope_interleave`: channel 2i turns with 2i + 1, in place.

The share, and the departures: as `reference_joyai.py`'s (the held experts
`first_expert .. first_expert + n_experts_held` of the router's `n_experts`;
a share's combine weights get no gradient; ids, logits and losses over a
slice of the vocabulary; the router bias's update rule is left out). What
the config does not give is listed in the configuration's `assumed`: the
ends of the path (copy in, sum out), no learned scale in a connection's
norm and the model's `rms_norm_eps` inside its root, `hc_eps` in Sinkhorn's
denominators, the clamp before the exponential, the MTP block's own
streams. Only the parameter layout (`models/mla_moe.py`; a connection's
`phi` [n, C, n + n + n^2], `alpha` [3] = (pre, post, res), `b`) is shared
with the code under test.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import _rms

_DEFAULTS = {
    "n_dense_layers": 1, "first_expert": 0, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rope_interleave": True, "mtp_depth": 1,
    "mtp_loss_coef": 0.1, "norm_eps": 1e-6, "rope_scaling": None,
    "hc_mult": 0, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "h_res_clamp_min": -30.0, "h_res_clamp_max": 30.0,
}


def _get(model, key):
    return model[key] if key in model else _DEFAULTS[key]


def _f(a):
    return a.astype(jnp.float32)


# --------------------------------------------------------------------------
# the connection
# --------------------------------------------------------------------------

def sinkhorn(M, iters, eps):
    """M [S, n, n] positive -> rows then columns normalised, `iters`
    times."""
    for _ in range(iters):
        M = M / (jnp.sum(M, axis=2, keepdims=True) + eps)   # rows
        M = M / (jnp.sum(M, axis=1, keepdims=True) + eps)   # columns
    return M


def hc_maps(X, p, model):
    """X [S, n, C] -> (H_pre [S, n], H_post [S, n], H_res [S, n, n])."""
    s, n, c = X.shape
    vec = X.reshape(s, n * c)
    xbar = vec / jnp.sqrt(jnp.mean(vec * vec, axis=1, keepdims=True)
                          + _get(model, "norm_eps"))
    a = xbar @ _f(p["phi"]).reshape(n * c, n + n + n * n)
    alpha, b = _f(p["alpha"]), _f(p["b"])
    a_pre, a_post, a_res = a[:, :n], a[:, n:2 * n], a[:, 2 * n:]
    b_pre, b_post, b_res = b[:n], b[n:2 * n], b[2 * n:]
    H_pre = jax.nn.sigmoid(alpha[0] * a_pre + b_pre)
    H_post = 2.0 * jax.nn.sigmoid(alpha[1] * a_post + b_post)
    lo, hi = _get(model, "h_res_clamp_min"), _get(model, "h_res_clamp_max")
    M = jnp.exp(jnp.clip(alpha[2] * a_res.reshape(s, n, n)
                         + b_res.reshape(n, n), lo, hi))
    return H_pre, H_post, sinkhorn(M, _get(model, "hc_sinkhorn_iters"),
                                   _get(model, "hc_eps"))


def connection(X, p, F, model):
    """X [S, n, C] -> (X' [S, n, C], what F gives beside y)."""
    H_pre, H_post, H_res = hc_maps(X, p, model)
    h = jnp.einsum("si,sic->sc", H_pre, X)
    y, aux = F(h)
    return (jnp.einsum("sij,sjc->sic", H_res, X)
            + H_post[:, :, None] * y[:, None, :]), aux


# --------------------------------------------------------------------------
# the sublayers: each is F(h) with its own pre-norm, no residual add
# --------------------------------------------------------------------------

def yarn_inv_freq(width, theta, factor, original, beta_fast, beta_slow):
    """float64 [width / 2]: HF `_compute_yarn_parameters`' blend."""
    plain = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)

    def pair_turning(times):
        return width * math.log(original / (times * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), width - 1)
    ramp = np.clip((np.arange(width // 2) - low) / max(high - low, 0.001),
                   0, 1)
    return plain / factor * ramp + plain * (1 - ramp)


def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary_table(model):
    """-> (inv_freq float32 [R / 2], the factor on cos and sin, the factor
    on the scores' scale)."""
    r, theta = model["qk_rope_head_dim"], model["rope_theta"]
    g = _get(model, "rope_scaling")
    if g is None:
        return jnp.asarray(theta ** (-np.arange(0, r, 2) / r),
                           jnp.float32), 1.0, 1.0
    g = dict(g)
    inv_freq = yarn_inv_freq(
        r, theta, g["factor"], g["original_max_position_embeddings"],
        g["beta_fast"], g["beta_slow"])
    all_dim = mscale(g["factor"], g["mscale_all_dim"])
    return (jnp.asarray(inv_freq, jnp.float32),
            mscale(g["factor"], g["mscale"]) / all_dim, all_dim ** 2)


def _rope(x, inv_freq, factor, interleave):
    """x [S, H, R] -> rotated by position; pairs (2i, 2i + 1) in place when
    `interleave`, else (i, i + R/2); cos and sin times `factor`."""
    s, _, r = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[:, None] * factor, jnp.sin(ang)[:, None] * factor
    if interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _swiglu(h, p):
    return (jax.nn.silu(h @ _f(p["w_gate"])) * (h @ _f(p["w_up"]))) \
        @ _f(p["w_down"])


def mla(x, p, model):
    """x [S, d] -> latent attention of rms(x) [S, d]."""
    eps = _get(model, "norm_eps")
    inter = _get(model, "rope_interleave")
    n_h, r_kv = model["n_heads"], model["kv_lora_rank"]
    n_nope, n_rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    inv_freq, on_cos_sin, on_scale = rotary_table(model)
    s = x.shape[0]
    h = _rms(x, _f(p["attn_norm"]), eps)
    c_q = _rms(h @ _f(p["wq_a"]), _f(p["q_norm"]), eps)
    q = (c_q @ _f(p["wq_b"]).reshape(c_q.shape[1], -1)).reshape(
        s, n_h, n_nope + n_rope)
    kv_a = h @ _f(p["wkv_a"])
    c_kv = _rms(kv_a[:, :r_kv], _f(p["kv_norm"]), eps)
    kv = (c_kv @ _f(p["wkv_b"]).reshape(r_kv, -1)).reshape(s, n_h, -1)
    q_nope = q[..., :n_nope]
    q_rope = _rope(q[..., n_nope:], inv_freq, on_cos_sin, inter)
    k_nope, v = kv[..., :n_nope], kv[..., n_nope:]
    k_rope = _rope(kv_a[:, None, r_kv:], inv_freq, on_cos_sin, inter)[:, 0]
    scale = (n_nope + n_rope) ** -0.5 * on_scale
    scores = (jnp.einsum("shk,thk->hst", q_nope, k_nope)
              + jnp.einsum("shk,tk->hst", q_rope, k_rope)) * scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    attn = jnp.einsum("hst,thk->shk", jax.nn.softmax(scores, -1), v)
    return attn.reshape(s, -1) @ _f(p["wo"]).reshape(-1, x.shape[1])


def route(h, p, model):
    """h [S, d] -> (dense weights [S, E]: a token's weight for each of ALL
    the router's experts, zero where not chosen; chosen [S, k])."""
    s = jax.nn.sigmoid(h @ _f(p["router"]))
    _, idx = jax.lax.top_k(
        s + jax.lax.stop_gradient(_f(p["router_bias"])),
        model["experts_per_token"])
    w = jnp.take_along_axis(s, idx, -1)
    if _get(model, "norm_topk_prob"):
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * _get(model, "routed_scaling_factor")
    if model.get("n_experts_held", s.shape[-1]) < s.shape[-1]:
        w = jax.lax.stop_gradient(w)   # a share's weights: constants
    return jnp.sum(jax.nn.one_hot(idx, s.shape[-1]) * w[..., None], 1), idx


def experts(x, p, model):
    """x [S, d] -> (routed part of the HELD experts + the shared expert's of
    rms(x) [S, d], chosen [S, k]); every held expert over every token."""
    h = _rms(x, _f(p["mlp_norm"]), _get(model, "norm_eps"))
    dense_w, idx = route(h, p, model)
    first = _get(model, "first_expert")
    ex = p["experts"]
    routed = jnp.zeros_like(h)
    for e in range(ex["w_gate"].shape[0]):
        routed = routed + dense_w[:, first + e:first + e + 1] * _swiglu(
            h, jax.tree.map(lambda a: a[e], ex))
    return routed + _swiglu(h, p["shared"]), idx


def dense_mlp(x, p, model):
    return _swiglu(_rms(x, _f(p["mlp_norm"]), _get(model, "norm_eps")), p)


# --------------------------------------------------------------------------
# layers, on n streams (or on one: `hc_mult` 0 is the plain residual path)
# --------------------------------------------------------------------------

def expert_layer(X, p, model):
    if not _get(model, "hc_mult"):
        x = X + mla(X, p, model)
        y, idx = experts(x, p, model)
        return x + y, idx
    X, _ = connection(X, p["hc_attn"], lambda h: (mla(h, p, model), None),
                      model)
    return connection(X, p["hc_mlp"], lambda h: experts(h, p, model), model)


def dense_layer(X, p, model):
    if not _get(model, "hc_mult"):
        x = X + mla(X, p, model)
        return x + dense_mlp(x, p, model)
    X, _ = connection(X, p["hc_attn"], lambda h: (mla(h, p, model), None),
                      model)
    return connection(X, p["hc_mlp"],
                      lambda h: (dense_mlp(h, p, model), None), model)[0]


def _enter(x, model):
    """x [S, C] -> the path's start: every stream a copy."""
    n = _get(model, "hc_mult")
    return jnp.repeat(x[:, None, :], n, axis=1) if n else x


def _leave(X, model):
    return jnp.sum(X, axis=1) if _get(model, "hc_mult") else X


def _forward(params, tokens, next_tokens, model):
    """tokens [S], next_tokens [S] or None -> (logits [S, V], MTP logits
    [S, V] or None, chosen experts per expert layer, the MTP block's
    last)."""
    eps = _get(model, "norm_eps")
    dense = jax.jit(lambda x, p: dense_layer(x, p, model))
    expert = jax.jit(lambda x, p: expert_layer(x, p, model))
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        X = _enter(_f(params["embed"][tokens]), model)
        n_dense = _get(model, "n_dense_layers")
        for i in range(n_dense):
            X = dense(X, at(params["dense"], i))
        chosen = []
        for i in range(model["n_layers"] - n_dense):
            X, idx = expert(X, at(params["layers"], i))
            chosen.append(idx)
        h = _rms(_leave(X, model), _f(params["final_norm"]), eps)
        head = _f(params["lm_head"])
        mtp_logits = None
        if _get(model, "mtp_depth") and next_tokens is not None:
            m = params["mtp"]
            x = jnp.concatenate(
                [_rms(_f(params["embed"][next_tokens]), _f(m["enorm"]), eps),
                 _rms(h, _f(m["hnorm"]), eps)], -1) @ _f(m["eh_proj"])
            X, idx = expert(_enter(x, model), at(m["block"], 0))
            chosen.append(idx)
            mtp_logits = _rms(_leave(X, model), _f(m["final_norm"]),
                              eps) @ head
        return h @ head, mtp_logits, chosen


def logits(params, tokens, model):
    """tokens [S] int -> next-token logits [S, vocab] float32."""
    return _forward(params, tokens, None, model)[0]


def routing(params, inputs, targets, model):
    """rows [R, S] -> chosen experts [layers (+ MTP), R * S, k], rows in
    order."""
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
