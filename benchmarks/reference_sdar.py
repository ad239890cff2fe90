"""Plain reference of SDAR's block-diffusion training loss over Qwen3-MoE
layers: float32 `jax.numpy`, `default_matmul_precision("highest")`, a DENSE
boolean [2L, 2L] mask, every held expert applied to every token, no kernel,
no sort, no gather of rows, no grouped matmul. Independent of the program:
nothing of `ray_tpu.models`, `ray_tpu.ops` or `ray_tpu.parallel` is
imported; the noise rule has its own lines here (numpy).

The layer (Qwen3-MoE, `modeling_sdar_moe.py` of JetLM/SDAR-30B-A3B-Chat),
for x [T, d] with positions pos [T] and the mask M [T, T]:

    a = rms(x, w_attn); q = a Wq [T, H, dh], k = a Wk, v = a Wv [T, KV, dh]
    q = rope(rms_dh(q) * g_q, pos), k = rope(rms_dh(k) * g_k, pos): the norm
    per head over its dh channels, ONE [dh] scale for all q heads and one
    for all kv heads; half-rotation pairs (i, i + dh/2), as `rotate_half`
    o = softmax(q k^T / sqrt(dh) + M) v, a kv head serving H / KV q heads
    h = x + o Wo
    m = rms(h, w_mlp); p = softmax(m Wr) over ALL E experts; top-k by p,
    w = p_top / sum(p_top) (`norm_topk_prob`)
    y = h + sum_j w_j Wd_j (silu(Wg_j m) * Wu_j m)

The objective (BD3-LM, arXiv:2503.09573, vectorised training), per row of
x_0 [L]:

    t ~ U(0, 1), p = (1 - eps) t + eps; token i becomes the mask id
    with probability p, independently -> x_t          (`noise`, below)
    tokens = [x_t ; x_0], pos = [0..L-1 ; 0..L-1], blk(i) = pos(i) // block
    i sees j iff (half(i) = half(j) and blk(i) = blk(j))
              or (j is clean and blk(j) < blk(i))     (`visible`, below)
    loss = sum over noised i of the x_t half of
           -log softmax(W_head rms(y_i))[x_0,i] / p, over the data tokens
         + aux_loss_coef * mean_l LB_l
    LB_l = E * sum_e f_e P_e over ALL 2L rows of the rows given TOGETHER,
    f_e = pairs sent to expert e / (T k), P_e = mean_t p_te.

Departures, each noted where it is made: (1) the share: with
`n_experts_held` < `n_experts` the sum over j runs over the chosen experts
that are held (`first_expert` ..), with the weights they have among all k,
and those weights are constants of the backward pass, as
`parallel/moe.moe_layer` documents for a share (the router still learns
from LB); (2) the per-layer mean of LB, as `reference_olmoe.py`; (3) the
weights are the program's, cast to float32, one layer at a time; attention
is computed one kv head's group at a time so that the float32 [H, 2L, 2L]
scores never exist at once; (4) the noise is the program's RULE (integer
hashes of the row's ids and `noise_seed`), written again here: a reference
with another draw would be another loss.

`params` is the program's pytree (`models/sdar.py` layout); `model` its
config fields as a dict. Only the layout is shared with the code under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import _rms


def _mix(x):
    """lowbias32 on uint32 arrays (numpy wraps silently on arrays)."""
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def _unit(h):
    return (h >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)


def noise(row, model):
    """row [L] ints -> (noised [L] bool, p float32): the rule of the module's
    docstring, a function of the row's ids and `noise_seed` alone."""
    ids = np.asarray(row).astype(np.uint32)
    at = np.arange(1, ids.shape[0] + 1, dtype=np.uint32) \
        * np.uint32(0x9E3779B9)
    key = _mix(np.array([model.get("noise_seed", 0)], np.uint32)
               + np.sum(_mix(ids + at), dtype=np.uint32))
    t = _unit(_mix(key ^ np.uint32(0xB5297A4D)))[0]
    eps = model.get("noise_eps", 1e-3)
    p = np.float32(1.0 - eps) * t + np.float32(eps)
    return _unit(_mix(key + at)) < p, p


def visible(length: int, block: int):
    """-> bool [2L, 2L]: row i may see column j."""
    pos = np.arange(2 * length)
    clean, blk = pos >= length, (pos % length) // block
    same = (clean[:, None] == clean[None, :]) & (blk[:, None] == blk[None, :])
    return same | (clean[None, :] & (blk[None, :] < blk[:, None]))


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, pos, mask, model):
    """x [T, d] float32 -> (x [T, d], router probabilities [T, E], chosen
    experts [T, k])."""
    f = lambda a: a.astype(jnp.float32)  # noqa: E731
    eps, theta = model["norm_eps"], model["rope_theta"]
    n_h, n_kv, d_h = model["n_heads"], model["n_kv_heads"], model["d_head"]
    a = _rms(x, f(p["attn_norm"]), eps)
    q = jnp.einsum("td,dhk->thk", a, f(p["wq"]))
    k = jnp.einsum("td,dhk->thk", a, f(p["wk"]))
    v = jnp.einsum("td,dhk->thk", a, f(p["wv"]))
    q = _rope(_rms(q, f(p["q_norm"]), eps), pos, theta)   # per head, [dh]
    k = _rope(_rms(k, f(p["k_norm"]), eps), pos, theta)

    def group(qkv):  # one kv head and the H / KV q heads it serves
        q_g, k_g, v_g = qkv
        s = jnp.einsum("trk,uk->rtu", q_g, k_g) / (d_h ** 0.5)
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("rtu,uk->trk", jax.nn.softmax(s, -1), v_g)

    rep = n_h // n_kv
    o = jax.lax.map(group, (
        q.reshape(-1, n_kv, rep, d_h).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))       # [KV, T, rep, dh]
    o = o.transpose(1, 0, 2, 3).reshape(-1, n_h, d_h)
    x = x + jnp.einsum("thk,hkd->td", o, f(p["wo"]))

    m = _rms(x, f(p["mlp_norm"]), eps)
    probs = jax.nn.softmax(m @ f(p["moe_gate"]), -1)
    w, idx = jax.lax.top_k(probs, model["experts_per_token"])
    if model.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    n_e = probs.shape[-1]
    n_held = model.get("n_experts_held") or n_e
    first = model.get("first_expert", 0)
    if n_held < n_e:
        # departure (1): a share's combine weights are constants
        w = jax.lax.stop_gradient(w)
    # [T, E]: a token's weight for each expert, zero where not chosen
    dense_w = jnp.sum(jax.nn.one_hot(idx, n_e) * w[..., None], 1)
    ex = p["experts"]
    for e in range(n_held):
        ff = jax.nn.silu(m @ f(ex["w_gate"][e])) * (m @ f(ex["w_up"][e]))
        x = x + dense_w[:, first + e:first + e + 1] * (ff @ f(ex["w_down"][e]))
    return x, probs, idx


def _forward(params, x_t, x_0, model):
    """x_t, x_0 [L] -> (logits of the x_t half [L, V], per layer (router
    probabilities [2L, E], chosen experts [2L, k]), the last layer's output
    [2L, d] before the final norm)."""
    length = x_0.shape[0]
    mask = jnp.asarray(visible(length, model.get("block", 4)))
    pos = jnp.tile(jnp.arange(length), 2)
    layer = jax.jit(lambda x, p: _layer(x, p, pos, mask, model))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.concatenate([x_t, x_0])].astype(jnp.float32)
        routed = []
        for i in range(model["n_layers"]):
            x, probs, idx = layer(
                x, jax.tree.map(lambda a: a[i], params["layers"]))
            routed.append((probs, idx))
        h = _rms(x[:length], params["final_norm"].astype(jnp.float32),
                 model["norm_eps"])
        return h @ params["lm_head"].astype(jnp.float32), routed, x


def _mask_id(model):
    m = model.get("mask_token_id")
    return model["vocab_size"] - 1 if m is None else m


def noised_rows(inputs, model):
    """rows [R, L] -> (x_t [R, L], noised [R, L] bool, p [R])."""
    rows = np.asarray(inputs)
    drawn = [noise(row, model) for row in rows]
    noised = np.stack([d[0] for d in drawn])
    return (np.where(noised, _mask_id(model), rows), noised,
            np.array([d[1] for d in drawn], np.float32))


def hidden_states(params, inputs, model):
    """rows [R, L] -> the last layer's output over [x_t ; x_0] [R, 2L, d],
    before the final norm."""
    x_t, _, _ = noised_rows(inputs, model)
    return jnp.stack([
        _forward(params, jnp.asarray(t), jnp.asarray(row), model)[2]
        for t, row in zip(x_t, np.asarray(inputs))])


def routing(params, inputs, model):
    """rows [R, L] -> chosen experts [layers, R * 2L, k], rows in order."""
    x_t, _, _ = noised_rows(inputs, model)
    per_row = [[idx for _, idx in _forward(
        params, jnp.asarray(t), jnp.asarray(row), model)[1]]
        for t, row in zip(x_t, np.asarray(inputs))]
    return jnp.stack([jnp.concatenate([row[i] for row in per_row])
                      for i in range(model["n_layers"])])


def loss_terms(params, inputs, model):
    """rows [R, L] given TOGETHER -> (the weighted CE over the data tokens,
    mean_l LB_l), float32 scalars (differentiable in params)."""
    rows = np.asarray(inputs)
    x_t, noised, p = noised_rows(rows, model)
    ce, routed = 0.0, []
    for r in range(rows.shape[0]):
        lg, per_layer, _ = _forward(
            params, jnp.asarray(x_t[r]), jnp.asarray(rows[r]), model)
        nll = -jnp.take_along_axis(
            jax.nn.log_softmax(lg, -1), jnp.asarray(rows[r])[:, None], -1)[:, 0]
        ce = ce + jnp.sum(jnp.where(noised[r], nll, 0.0)) / p[r]
        routed.append(per_layer)
    lb = 0.0
    for i in range(model["n_layers"]):
        probs = jnp.concatenate([row[i][0] for row in routed])   # [T, E]
        idx = jnp.concatenate([row[i][1] for row in routed])     # [T, k]
        n_e = probs.shape[-1]
        f_e = jnp.bincount(idx.reshape(-1), length=n_e) / idx.size
        lb = lb + n_e * jnp.sum(f_e * jnp.mean(probs, 0))
    return ce / rows.size, lb / model["n_layers"]


def loss_value(params, inputs, model):
    """The training loss over rows [R, L] given together, float32 scalar."""
    ce, lb = loss_terms(params, inputs, model)
    return ce + model.get("aux_loss_coef", 0.001) * lb


def loss(params, inputs, targets, model):
    """`loss_value` as a python float (the harness's contract; `targets`,
    the next tokens, are not read: position i predicts token i)."""
    return float(loss_value(params, inputs, model))
