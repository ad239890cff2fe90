"""Plain reference of EvaByte's language model (`model_type` `evabyte`,
`attention_class` `eva`: EVA attention, a float32 residual stream, a norm
with a unit offset, several next-byte heads) and its training loss: float32
`jax.numpy`, `default_matmul_precision("highest")`, no kernels; one jitted
layer at a time, attention a head at a time in blocks of `_BLOCK` query rows
against a DENSE mask over all the keys, the MLP in blocks of `_ROWS` rows,
so that it fits at the published widths and S 32,768. Nothing of `ray_tpu`
is imported.

The layer equations are the paper's (Zheng, Yuan, Wang, Kong, "Efficient
Attention via Control Variates", ICLR 2023) in the simplified form EvaByte's
released `eva.py` ships (`eva_prep_kv`, `eva_agg`): a learned pooling vector
phi and a learned offset mu a head in place of sampled features. For one row
of S bytes, with c(j) = j // chunk, w(n) = n // window, s = d_head ** -0.5:

    x_0 = E[bytes]                         float32 throughout
    layer:  x = x + W_o EVA(norm(x))
            x = x + W_down (silu(W_gate h) * W_up h),  h = norm(x)
    norm(x) = x / sqrt(mean(x^2) + eps) * (1 + g)
    q, k, v = heads of W_q h, W_k h, W_v h; RoPE (theta, every channel, pairs
              (d, d + d_head / 2)) on q and k BEFORE the pooling
    a head and chunk c:  p_j = softmax over the chunk's bytes of (phi . k_j)
        k~_c = sum_j p_j k_j + mu          v~_c = sum_j p_j v_j
    query n, ONE softmax over
        its window's own bytes  {j : w(j) = w(n), j <= n}   s q_n . k_j, v_j
        EARLIER windows' chunks {c : w(c chunk) < w(n)}     s q_n . k~_c, v~_c
    logits_i = norm(x_L) W_head[:, i V : (i + 1) V]          i = 0 .. heads - 1
    loss = mean over i of the mean, over the positions t with t + i < S, of
           CE(logits_i[t], targets[t + i])       (targets[t] the byte after t)

Departures from the published description: (1) the layers held (the
configuration says how many); (2) every `assumed` of the configuration file
(the pooling logits without the softmax scale, equal weights over the heads
and a mean over each head's valid positions); (3) the weights are the
program's, cast to float32, a layer at a time. Only the parameter layout
(`models/evabyte.py`) is shared with the code under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_BLOCK = 512    # query rows of attention at a time
_ROWS = 4096    # rows of the MLP at a time


def _f(a):
    return a.astype(jnp.float32)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, theta):
    """x [S, H, D]: position t turns the pair (d, d + D / 2) by the angle
    t * theta ** (-2 d / D)."""
    s, _, d = x.shape
    half = d // 2
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def summaries(k, v, phi, mu, chunk):
    """k, v [S, H, D], phi, mu [H, D] -> k~, v~ [S / chunk, H, D]: an
    explicit softmax over each chunk's rows."""
    s, h, d = k.shape
    kc = k.reshape(s // chunk, chunk, h, d)
    vc = v.reshape(s // chunk, chunk, h, d)
    pool = jnp.einsum("cjhd,hd->cjh", kc, phi)
    pool = jnp.exp(pool - jnp.max(pool, axis=1, keepdims=True))
    pool = pool / jnp.sum(pool, axis=1, keepdims=True)
    return (jnp.einsum("cjh,cjhd->chd", pool, kc) + mu,
            jnp.einsum("cjh,cjhd->chd", pool, vc))


def visible(n, s, window, chunk):
    """Which keys the queries at byte positions n [rows, 1] see, of S bytes
    -> (earlier [rows, S / chunk], own [rows, S]): a chunk summary where the
    chunk's window is EARLIER than the query's, a byte where it is in the
    query's own window and no later than the query."""
    j = jnp.arange(s)[None, :]
    c = jnp.arange(s // chunk)[None, :]
    own = (j // window == n // window) & (j <= n)
    earlier = (c * chunk) // window < n // window
    return earlier, own


def attend(scores, kept, values):
    """ONE softmax over the kept keys of both kinds: scores, kept [rows,
    keys], values [keys, D] -> [rows, D]."""
    return jax.nn.softmax(jnp.where(kept, scores, -jnp.inf), -1) @ values


def attention(h, p, model):
    """h [S, d] -> W_o EVA(h) [S, d]."""
    window, chunk = model["window"], model["chunk"]
    q = _rope(jnp.einsum("sd,dhk->shk", h, p["wq"]), model["rope_theta"])
    k = _rope(jnp.einsum("sd,dhk->shk", h, p["wk"]), model["rope_theta"])
    v = jnp.einsum("sd,dhk->shk", h, p["wv"])
    s, _, d = q.shape
    k_sum, v_sum = summaries(k, v, p["phi"], p["mu"], chunk)
    keys = jnp.concatenate([k_sum, k], 0)       # [chunks + S, H, D]
    values = jnp.concatenate([v_sum, v], 0)
    block = min(_BLOCK, s)
    if s % block:
        raise ValueError(f"S {s} is not whole blocks of {block} rows")

    def head(qkv):
        q_h, keys_h, values_h = qkv             # [S, D], [chunks + S, D] x 2

        def rows(at):
            kept = jnp.concatenate(visible(
                at + jnp.arange(block)[:, None], s, window, chunk), -1)
            q_blk = jax.lax.dynamic_slice_in_dim(q_h, at, block)
            return attend((q_blk @ keys_h.T) * d ** -0.5, kept, values_h)

        return jax.lax.map(rows, jnp.arange(0, s, block)).reshape(s, d)

    out = jax.lax.map(head, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, keys, values)))   # [H, S, D]
    return jnp.einsum("hsk,hkd->sd", out, p["wo"])


def mlp(h, p):
    out = [(jax.nn.silu(h[at:at + _ROWS] @ p["w_gate"])
            * (h[at:at + _ROWS] @ p["w_up"])) @ p["w_down"]
           for at in range(0, h.shape[0], _ROWS)]
    return jnp.concatenate(out, 0)


def layer(x, p, model):
    p = jax.tree.map(_f, p)
    eps = model["norm_eps"]
    x = x + attention(_norm(x, p["attn_norm"], eps), p, model)
    return x + mlp(_norm(x, p["mlp_norm"], eps), p)


def hidden(params, tokens, model):
    """tokens [S] -> the final norm's output [S, d]."""
    run = jax.jit(lambda x, p: layer(x, p, model))
    x = _f(params["embed"][tokens])
    for i in range(model["n_layers"]):
        x = run(x, jax.tree.map(lambda a: a[i], params["layers"]))
    return _norm(x, _f(params["final_norm"]), model["norm_eps"])


def logits(params, tokens, model):
    """tokens [S] int -> [S, heads, vocab] float32: head i at position t
    scores the byte 1 + i on."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, model)
        return (h @ _f(params["lm_head"])).reshape(
            h.shape[0], model["pred_heads"], model["vocab_size"])


def head_targets(row_t, i):
    """Head i's targets over the positions that have one: at t, the byte 1 +
    i on, which is targets[t + i]; the last i positions have none."""
    return row_t[i:]


def loss_value(params, inputs, targets, model):
    """The training loss over rows [R, S], float32 scalar (differentiable):
    the heads one after the other, each its mean over the positions that
    have a target, then the mean over the heads."""
    heads, vocab = model["pred_heads"], model["vocab_size"]
    with jax.default_matmul_precision("highest"):
        head = _f(params["lm_head"])
        hs = [hidden(params, row, model) for row in inputs]
        total = 0.0
        for i in range(heads):
            w_i = head[:, i * vocab:(i + 1) * vocab]
            nll, count = 0.0, 0
            for h, row_t in zip(hs, targets):
                want = head_targets(row_t, i)
                logp = jax.nn.log_softmax(h[:want.shape[0]] @ w_i, -1)
                nll = nll - jnp.sum(jnp.take_along_axis(
                    logp, want[:, None], -1))
                count += want.shape[0]
            total = total + nll / count
    return total / heads


def loss(params, inputs, targets, model):
    """`loss_value` as a python float (the harness's contract)."""
    return float(loss_value(params, inputs, targets, model))
