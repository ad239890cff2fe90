"""Plain reference of the OLMoE block and its training loss: float32
`jax.numpy`, `default_matmul_precision("highest")`, no kernels, no sort, no
gather, no grouped matmul, no cache.

Follows Hugging Face `modeling_olmoe.py` (allenai/OLMoE-1B-7B-0125) for the
layer and the OLMoE paper (arXiv:2409.02060) for the loss. For x [S, d]:

    h = rms(x, w_attn); q = rms(h Wq, w_qn) over ALL q channels,
    k = rms(h Wk, w_kn) over all k channels, v = h Wv; split into heads;
    RoPE (half-rotation pairing (i, i + d/2), as `rotate_half`) on q and k;
    causal softmax(q k^T / sqrt(d_head)) v; x = x + attn Wo
    h = rms(x, w_mlp); r = h Wr; p = softmax(r); (w, idx) = top_k(p),
    divided by their sum only if `norm_topk_prob`;
    y = sum_j w_j * (silu(h Wg[idx_j]) * (h Wu[idx_j])) Wd[idx_j]; x = x + y

computed with EVERY expert applied to every token and multiplied by that
token's weight for it (zero where not chosen). Final RMSNorm, untied head.

    loss = CE + aux_loss_coef * mean_l LB_l + router_z_loss_coef * mean_l RZ_l
    LB_l = E * sum_i f_i P_i,  f_i = pairs sent to expert i / (T k),
    P_i = mean_t p_ti;  RZ_l = mean_t logsumexp_i(r_ti)^2

over all T tokens of the rows given TOGETHER (the aux terms are batch
statistics). Departures: (1) the per-layer mean of LB (Hugging Face's
`load_balancing_loss_func` concatenates the layers' tokens and sums over
the k slots instead; the configuration lists this under `assumed`); (2) the
weights are the program's, cast to float32, one layer at a time; (3) with
`qk_norm` false and `norm_topk_prob` true the same code is Mixtral's block.

`params` is the program's pytree (`models/mixtral.py` layout); `model` its
config fields as a dict. Only the layout is shared with the code under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import _rms, _rope


def _layer(x, p, model):
    """x [S, d] float32; p one layer's weights (any float dtype) ->
    (x [S, d], router logits r [S, E], chosen experts [S, k])."""
    f = lambda a: a.astype(jnp.float32)  # noqa: E731
    eps, theta = model["norm_eps"], model["rope_theta"]
    n_h, n_kv, d_h = model["n_heads"], model["n_kv_heads"], model["d_head"]
    s = x.shape[0]
    h = _rms(x, f(p["attn_norm"]), eps)
    q = h @ f(p["wq"]).reshape(-1, n_h * d_h)
    k = h @ f(p["wk"]).reshape(-1, n_kv * d_h)
    v = h @ f(p["wv"]).reshape(-1, n_kv * d_h)
    if model.get("qk_norm", False):
        q = _rms(q, f(p["q_norm"]).reshape(-1), eps)
        k = _rms(k, f(p["k_norm"]).reshape(-1), eps)
    q = _rope(q.reshape(s, n_h, d_h), theta)
    k = _rope(k.reshape(s, n_kv, d_h), theta)
    v = v.reshape(s, n_kv, d_h)
    k, v = (jnp.repeat(a, n_h // n_kv, axis=1) for a in (k, v))
    scores = jnp.einsum("shk,thk->hst", q, k) / (d_h ** 0.5)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    attn = jnp.einsum("hst,thk->shk", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(s, n_h * d_h) @ f(p["wo"]).reshape(n_h * d_h, -1)

    h = _rms(x, f(p["mlp_norm"]), eps)
    r = h @ f(p["moe_gate"])
    w, idx = jax.lax.top_k(jax.nn.softmax(r, -1), model["experts_per_token"])
    if model.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    # [S, E]: a token's weight for each expert, zero where not chosen
    dense_w = jnp.sum(jax.nn.one_hot(idx, r.shape[-1]) * w[..., None], 1)
    ex = p["experts"]
    for e in range(r.shape[-1]):
        ff = jax.nn.silu(h @ f(ex["w_gate"][e])) * (h @ f(ex["w_up"][e]))
        x = x + dense_w[:, e:e + 1] * (ff @ f(ex["w_down"][e]))
    return x, r, idx


def _forward(params, tokens, model):
    """tokens [S] -> (logits [S, V], [(r, idx) per layer])."""
    layer = jax.jit(lambda x, p: _layer(x, p, model))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        routed = []
        for i in range(model["n_layers"]):
            x, r, idx = layer(x, jax.tree.map(lambda a: a[i],
                                              params["layers"]))
            routed.append((r, idx))
        x = _rms(x, params["final_norm"].astype(jnp.float32),
                 model["norm_eps"])
        return x @ params["lm_head"].astype(jnp.float32), routed


def logits(params, tokens, model):
    """tokens [S] int -> logits [S, vocab] float32: the full forward pass."""
    return _forward(params, tokens, model)[0]


def routing(params, inputs, model):
    """rows [R, S] -> chosen experts [L, R * S, k], rows in order."""
    per_row = [[idx for _, idx in _forward(params, row, model)[1]]
               for row in inputs]
    return jnp.stack([jnp.concatenate([row[i] for row in per_row])
                      for i in range(model["n_layers"])])


def loss_terms(params, inputs, targets, model):
    """rows [R, S] -> (mean CE, mean_l LB_l, mean_l RZ_l), float32 scalars
    (differentiable), the batch statistics over all R * S tokens."""
    nll, count, routed = 0.0, 0, []
    for row_in, row_t in zip(inputs, targets):
        lg, per_layer = _forward(params, row_in, model)
        logp = jax.nn.log_softmax(lg, -1)
        nll = nll - jnp.sum(jnp.take_along_axis(logp, row_t[:, None], -1))
        count += int(row_t.shape[0])
        routed.append(per_layer)
    lb = rz = 0.0
    for i in range(model["n_layers"]):
        r = jnp.concatenate([row[i][0] for row in routed])      # [T, E]
        idx = jnp.concatenate([row[i][1] for row in routed])    # [T, k]
        n_e = r.shape[-1]
        f_i = jnp.bincount(idx.reshape(-1), length=n_e) / idx.size
        p_i = jnp.mean(jax.nn.softmax(r, -1), 0)
        lb = lb + n_e * jnp.sum(f_i * p_i)
        rz = rz + jnp.mean(jax.nn.logsumexp(r, -1) ** 2)
    return nll / count, lb / model["n_layers"], rz / model["n_layers"]


def loss_value(params, inputs, targets, model):
    """The training loss over rows [R, S] given together, float32 scalar."""
    ce, lb, rz = loss_terms(params, inputs, targets, model)
    return (ce + model.get("aux_loss_coef", 0.01) * lb
            + model.get("router_z_loss_coef", 0.0) * rz)


def loss(params, inputs, targets, model):
    """`loss_value` as a python float (the harness's contract)."""
    return float(loss_value(params, inputs, targets, model))
