"""Plain reference of the Mistral-7B block: float32 `jax.numpy`, no
kernels, no cache, no batching tricks, `default_matmul_precision("highest")`.

Follows the published architecture (mistralai/Mistral-7B-v0.3): pre-norm
RMSNorm, grouped-query attention with rotary embeddings (half-rotation
pairing (i, i + d/2), as Hugging Face's `rotate_half`), causal softmax,
SwiGLU MLP, untied lm_head. Departure: none in the mathematics; the
weights are the program's bf16 weights cast to float32, taken one layer at
a time so that the float32 copy of a 7B-width stack never exists at once.

`params` is the program's pytree (`models/llama.py` layout: stacked
layers); only its layout is shared with the code under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, half = x.shape[0], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, model):
    """x [S, d] float32; p one layer's weights (any float dtype)."""
    f = lambda a: a.astype(jnp.float32)  # noqa: E731
    eps, theta = model["norm_eps"], model["rope_theta"]
    rep = model["n_heads"] // model["n_kv_heads"]
    h = _rms(x, f(p["attn_norm"]), eps)
    q = _rope(jnp.einsum("sd,dhk->shk", h, f(p["wq"])), theta)
    k = _rope(jnp.einsum("sd,dhk->shk", h, f(p["wk"])), theta)
    v = jnp.einsum("sd,dhk->shk", h, f(p["wv"]))
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("shk,thk->hst", q, k) / (model["d_head"] ** 0.5)
    s = x.shape[0]
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    attn = jnp.einsum("hst,thk->shk", jax.nn.softmax(scores, -1), v)
    x = x + jnp.einsum("shk,hkd->sd", attn, f(p["wo"]))
    h = _rms(x, f(p["mlp_norm"]), eps)
    ff = jax.nn.silu(h @ f(p["w_gate"])) * (h @ f(p["w_up"]))
    return x + ff @ f(p["w_down"])


def logits(params, tokens, model):
    """tokens [S] int -> logits [S, vocab] float32: the full forward pass."""
    layer = jax.jit(lambda x, p: _layer(x, p, model))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for i in range(model["n_layers"]):
            x = layer(x, jax.tree.map(lambda a: a[i], params["layers"]))
        x = _rms(x, params["final_norm"].astype(jnp.float32),
                 model["norm_eps"])
        return x @ params["lm_head"].astype(jnp.float32)


def loss(params, inputs, targets, model):
    """Mean next-token cross-entropy over rows [R, S] -> python float."""
    total, count = 0.0, 0
    for row_in, row_t in zip(inputs, targets):
        logp = jax.nn.log_softmax(logits(params, row_in, model), -1)
        nll = -jnp.take_along_axis(logp, row_t[:, None], -1)[:, 0]
        total += float(jnp.sum(nll))
        count += int(row_t.shape[0])
    return total / count
