"""Plain reference of the JoyAI-LLM-Flash decoder (the DeepSeek-V3 layer
form) and its training loss: float32 `jax.numpy`,
`default_matmul_precision("highest")`, no kernels, no sort, no gather of
rows, no grouped matmul, no cache; one jitted layer at a time so that it
fits at the published widths.

Follows the published `config.json` (jdopensource/JoyAI-LLM-Flash,
`model_type` joyai_llm_flash) and Hugging Face `modeling_deepseek_v3.py`
for the layer, DeepSeek-V3's report (arXiv:2412.19437) for MTP. For one row
x [S, d], every layer pre-norm:

    MLA: h = rms(x); c_q = rms(h W_qa); [q_nope | q_rope] = c_q W_qb per
    head (128 | 64); [c_kv | k_rope] = h W_kva (512 | 64), c_kv = rms(c_kv);
    [k_nope | v] = c_kv W_kvb per head (128 | 128); RoPE on q_rope and on
    the ONE k_rope all heads share; causal softmax((q_nope.k_nope +
    q_rope.k_rope) / sqrt(192)) v; x = x + concat(heads) W_o.
    RoPE: `rope_interleave` true, so channel 2i turns with channel 2i + 1,
    in place (the program brings the even channels in front of the odd ones
    first and turns halves: the same products).
    Dense layers (the first `n_dense_layers`): x = x + SwiGLU(rms(x)).
    Expert layers: h = rms(x); s = sigmoid(h W_r); chosen = top_k(s + b)
    (b gets no gradient; `n_group` 1: no group limit); w = s[chosen] /
    sum(s[chosen]) * routed_scaling_factor; x = x + sum_j w_j E_j(h) +
    E_shared(h), every expert a SwiGLU, computed with EVERY HELD expert
    applied to every token and multiplied by that token's weight for it
    (zero where not chosen).
    Final RMSNorm, untied head: CE of t_{i+1}.
    MTP: h'_i = [rms_e(Emb(t_{i+1})) | rms_h(h_i)] W_eh with h_i the main
    model's final-norm output, one expert layer of its own, its own final
    norm, the shared embedding and head: CE_mtp of t_{i+2} over positions
    0 .. S - 2. loss = CE + mtp_loss_coef * CE_mtp.

The share: `params` holds the experts `first_expert .. first_expert +
n_experts_held` of the router's `n_experts`; the top-k and the normalisation
run over all `n_experts`, the sum over the chosen experts that are held.
What the absent ones would add is left out, here as in the program.

Departures from the published description: (1) the share above, and ids,
logits and both losses over a slice of the vocabulary (the configuration
says so); (2) `mtp_loss_coef`, the feeding of the FINAL-NORM hidden state
to the MTP block and the order [embedding | hidden] of its concatenation
are assumptions (the config gives none; listed in the configuration's
`assumed`); (3) the router bias's update rule is no part of the loss and is
left out; (4) the weights are the program's, cast to float32, a layer at a
time; (5) on a share (fewer experts held than the router scores) the
combine weights w get no gradient: the held experts' term of the router's
gradient, without the absent experts' terms, only says "held experts
answer" and drives all tokens onto them within ~15 steps (PERF.md section 6,
PR 32); with every expert held the router trains as published. Only the
parameter layout (`models/mla_moe.py`) is shared with the code under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import _rms

_DEFAULTS = {
    "n_dense_layers": 1, "first_expert": 0, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "rope_interleave": True, "mtp_depth": 1,
    "mtp_loss_coef": 0.1, "norm_eps": 1e-6,
}


def _get(model, key):
    return model[key] if key in model else _DEFAULTS[key]


def _f(a):
    return a.astype(jnp.float32)


def _rope(x, theta, interleave):
    """x [S, H, R] -> rotated by position; pairs (2i, 2i + 1) in place when
    `interleave`, else (i, i + R/2)."""
    s, _, r = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(r // 2, dtype=jnp.float32) / (r // 2)))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]  # [S, 1, R/2]
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
    """x [S, d] -> x + latent attention of rms(x)."""
    eps, theta = _get(model, "norm_eps"), model["rope_theta"]
    inter = _get(model, "rope_interleave")
    n_h, r_kv = model["n_heads"], model["kv_lora_rank"]
    n_nope, n_rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    s = x.shape[0]
    h = _rms(x, _f(p["attn_norm"]), eps)
    c_q = _rms(h @ _f(p["wq_a"]), _f(p["q_norm"]), eps)
    q = (c_q @ _f(p["wq_b"]).reshape(c_q.shape[1], -1)).reshape(
        s, n_h, n_nope + n_rope)
    kv_a = h @ _f(p["wkv_a"])
    c_kv = _rms(kv_a[:, :r_kv], _f(p["kv_norm"]), eps)
    kv = (c_kv @ _f(p["wkv_b"]).reshape(r_kv, -1)).reshape(s, n_h, -1)
    q_nope, q_rope = q[..., :n_nope], _rope(q[..., n_nope:], theta, inter)
    k_nope, v = kv[..., :n_nope], kv[..., n_nope:]
    k_rope = _rope(kv_a[:, None, r_kv:], theta, inter)[:, 0]   # [S, R]
    scores = (jnp.einsum("shk,thk->hst", q_nope, k_nope)
              + jnp.einsum("shk,tk->hst", q_rope, k_rope)) \
        / ((n_nope + n_rope) ** 0.5)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    attn = jnp.einsum("hst,thk->shk", jax.nn.softmax(scores, -1), v)
    return x + attn.reshape(s, -1) @ _f(p["wo"]).reshape(-1, x.shape[1])


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
        w = jax.lax.stop_gradient(w)   # departure (5): a share's weights
    return jnp.sum(jax.nn.one_hot(idx, s.shape[-1]) * w[..., None], 1), idx


def experts(h, p, model):
    """h [S, d] (normed) -> (routed part of the HELD experts [S, d], shared
    expert's part [S, d], chosen [S, k])."""
    dense_w, idx = route(h, p, model)
    first = _get(model, "first_expert")
    ex = p["experts"]
    routed = jnp.zeros_like(h)
    for e in range(ex["w_gate"].shape[0]):
        routed = routed + dense_w[:, first + e:first + e + 1] * _swiglu(
            h, jax.tree.map(lambda a: a[e], ex))
    return routed, _swiglu(h, p["shared"]), idx


def expert_layer(x, p, model):
    x = mla(x, p, model)
    routed, shared, idx = experts(
        _rms(x, _f(p["mlp_norm"]), _get(model, "norm_eps")), p, model)
    return x + routed + shared, idx


def dense_layer(x, p, model):
    x = mla(x, p, model)
    return x + _swiglu(_rms(x, _f(p["mlp_norm"]), _get(model, "norm_eps")), p)


def _forward(params, tokens, next_tokens, model):
    """tokens [S], next_tokens [S] or None -> (logits [S, V], MTP logits
    [S, V] or None, chosen experts per expert layer, the MTP block's
    last)."""
    eps = _get(model, "norm_eps")
    dense = jax.jit(lambda x, p: dense_layer(x, p, model))
    expert = jax.jit(lambda x, p: expert_layer(x, p, model))
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = _f(params["embed"][tokens])
        n_dense = _get(model, "n_dense_layers")
        for i in range(n_dense):
            x = dense(x, at(params["dense"], i))
        chosen = []
        for i in range(model["n_layers"] - n_dense):
            x, idx = expert(x, at(params["layers"], i))
            chosen.append(idx)
        h = _rms(x, _f(params["final_norm"]), eps)
        head = _f(params["lm_head"])
        mtp_logits = None
        if _get(model, "mtp_depth") and next_tokens is not None:
            m = params["mtp"]
            x = jnp.concatenate(
                [_rms(_f(params["embed"][next_tokens]), _f(m["enorm"]), eps),
                 _rms(h, _f(m["hnorm"]), eps)], -1) @ _f(m["eh_proj"])
            x, idx = expert(x, at(m["block"], 0))
            chosen.append(idx)
            mtp_logits = _rms(x, _f(m["final_norm"]), eps) @ head
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
