"""The sequence mixers more than one model module uses, beside `blocks.py`'s
attention. A mixer moves here when a SECOND model takes it; one model's own
(EVA in `evabyte.py`) stays in its module.

- *MLA*, DeepSeek-V3's latent attention (`mla_moe`, `hybrid_moe`):
  `mla_sublayer`, with `mla_axes`, `init_mla`, `mla_num_params`.
- *KDA*, Kimi Delta Attention through `ops/kda.py` (`hybrid_moe`,
  `solar_open2`): `kda_sublayer`, with `kda_axes`, `init_kda`,
  `kda_num_params`; the decay gate's form, the gates' rank and beta's scale
  are fields of the caller's config.
- *Mamba-2* (`nemotron_h`, `granite_hybrid`) through `ops/ssd.py`:
  `mamba_sublayer`. `mixer_axes`, `init_mixer`, `mixer_num_params` give a
  layer of kind `M` (this mixer and its norm) or `*` (`blocks.attn_sublayer`
  at `n_heads` / `n_kv_heads` heads of `d_head`), the two kinds both models'
  patterns are made of.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler
from ray_tpu.models import blocks
from ray_tpu.models.blocks import rms_norm, rope
from ray_tpu.ops import kda as kda_op
from ray_tpu.ops import kda_prep
from ray_tpu.ops import ssd as ssd_op
from ray_tpu.parallel.sharding import LogicalAxisRules

# --------------------------------------------------------------------------
# MLA
# --------------------------------------------------------------------------

def mla_num_params(c) -> int:
    """The mixer's parameters (no layer norm) under config `c`."""
    d_qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    q = (c.d_model * c.q_lora_rank + c.q_lora_rank
         + c.q_lora_rank * c.n_heads * d_qk) if c.q_lora_rank \
        else c.d_model * c.n_heads * d_qk
    return (q + c.d_model * (c.kv_lora_rank + c.qk_rope_head_dim)
            + c.kv_lora_rank + c.kv_lora_rank * c.n_heads
            * (c.qk_nope_head_dim + c.v_head_dim)
            + c.n_heads * c.v_head_dim * c.d_model
            + (2 * d_qk if c.qk_head_norm else 0)
            + (c.d_model * c.n_heads if c.attn_gate else 0))


def mla_axes(L, config):
    q = {"wq_a": L + ("embed", None), "q_norm": L + (None,),
         "wq_b": L + (None, "heads", "kv")} if config.q_lora_rank \
        else {"wq": L + ("embed", "heads", "kv")}
    if config.qk_head_norm:
        q.update(q_head_norm=L + (None,), k_head_norm=L + (None,))
    if config.attn_gate:
        q["w_attn_gate"] = L + ("embed", "heads")
    return {
        "attn_norm": L + (None,), **q,
        "wkv_a": L + ("embed", None), "kv_norm": L + (None,),
        "wkv_b": L + (None, "heads", "kv"),
        "wo": L + ("heads", "kv", "embed"),
        "mlp_norm": L + (None,),
    }


def init_mla(config, key):
    """One layer's mixer and its two layer norms."""
    c = config
    d_qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    ones = partial(jnp.ones, dtype=c.dtype)
    dense = partial(blocks.dense, c)
    ks = jax.random.split(key, 5)
    if c.q_lora_rank:
        q = {"wq_a": dense(ks[0], (c.d_model, c.q_lora_rank), c.d_model),
             "q_norm": ones((c.q_lora_rank,)),
             "wq_b": dense(ks[1], (c.q_lora_rank, c.n_heads, d_qk),
                           c.q_lora_rank)}
    else:
        q = {"wq": dense(ks[0], (c.d_model, c.n_heads, d_qk), c.d_model)}
    if c.qk_head_norm:
        q.update(q_head_norm=ones((d_qk,)), k_head_norm=ones((d_qk,)))
    if c.attn_gate:
        q["w_attn_gate"] = dense(jax.random.fold_in(key, 5),
                                 (c.d_model, c.n_heads), c.d_model)
    return {
        "attn_norm": ones((c.d_model,)), **q,
        "wkv_a": dense(ks[2], (c.d_model, c.kv_lora_rank
                               + c.qk_rope_head_dim), c.d_model),
        "kv_norm": ones((c.kv_lora_rank,)),
        "wkv_b": dense(ks[3], (c.kv_lora_rank, c.n_heads,
                               c.qk_nope_head_dim + c.v_head_dim),
                       c.kv_lora_rank),
        "wo": dense(ks[4], (c.n_heads, c.v_head_dim, c.d_model),
                    c.n_heads * c.v_head_dim),
        "mlp_norm": ones((c.d_model,)),
    }


def interleaved(w, config):
    """With `rope_interleave` channel 2i turns with 2i + 1: the even
    channels of the last dim are brought in front of the odd ones, so that
    `blocks.rope` turns (i, i + R/2). A permutation of a linear map's
    output channels, so it is applied to the weights that make the rotary
    parts (6 MiB) and not to their [B, S, H, R] outputs. The rotary parts
    stay in that order; q and k get the same treatment, so their products
    are the interleaved form's."""
    if not config.rope_interleave:
        return w
    return jnp.concatenate([w[..., 0::2], w[..., 1::2]], axis=-1)


def mla_mixer(h, p, positions, config, mesh=None, rotary=None, scale=None):
    """MLA on h [B, S, D], a layer's normed input -> [B, S, D], before the
    residual is added (`mla_sublayer` adds it; `models/streams.py` spreads
    it over the streams). `rotary` (`blocks.Rotary`): the rotary parts'
    form where it is not plain RoPE at `config.rope_theta` (YaRN's blended
    frequencies); `scale`: the scores' where it is not (128 + 64) ** -0.5
    (DeepSeek's `mscale` under YaRN).

    The flash call takes its operands in the parts the projections make:
    `wq_b` [r, H, 128 + 64] and `wkv_b` [r, H, 128 + 128] stay the published
    parameters and are used by slices of the WEIGHT, so q, the rotary q, k
    and v are each a dot's own output: no [B, S, H, 192] q or k is built,
    nothing is cut out of a [B, S, H, 256] k|v, the rotary key is not
    copied to H heads (`flash_attention` in parts; it sums that key's
    gradient over heads itself) and the interleave is a permutation of
    weight columns (`interleaved`). Under remat "dots" the saved residuals
    are then the call's operands themselves (the rotary q before RoPE),
    and `blocks.checkpointed` saves its result beside them."""
    c = config
    n_nope, n_lat = c.qk_nope_head_dim, c.kv_lora_rank
    with jax.named_scope("mla.latents"):
        up = partial(jnp.einsum, "bsr,rhk->bshk")
        if c.q_lora_rank:
            c_q = rms_norm(h @ p["wq_a"], p["q_norm"], c.norm_eps)
            w_q = p["wq_b"]
        else:  # no q latent: the projection straight from the layer input
            c_q, w_q = h, p["wq"]

        def head_norm(x, name, rotary):
            # the per-head norm, a part at a time (so the ONE rotary key
            # stays every head's); the rotary channels' scales in the
            # order `_interleaved` leaves the channels in
            if not c.qk_head_norm:
                return x
            scale = interleaved(p[name][n_nope:], c) if rotary \
                else p[name][:n_nope]
            return rms_norm(x, scale, c.norm_eps)

        turned = lambda x, name: rope(  # noqa: E731
            head_norm(x, name, True), positions, c.rope_theta, rotary)
        q = head_norm(up(c_q, w_q[..., :n_nope]), "q_head_norm", False)
        q_rope = turned(up(c_q, interleaved(w_q[..., n_nope:], c)),
                        "q_head_norm")
        kv_a = h @ jnp.concatenate(
            [p["wkv_a"][:, :n_lat], interleaved(p["wkv_a"][:, n_lat:], c)],
            axis=-1)
        c_kv = rms_norm(kv_a[..., :n_lat], p["kv_norm"], c.norm_eps)
        k = head_norm(up(c_kv, p["wkv_b"][..., :n_nope]), "k_head_norm",
                      False)
        v = up(c_kv, p["wkv_b"][..., n_nope:])
        # one rotary key, the same for every head
        k_rope = turned(kv_a[..., None, n_lat:], "k_head_norm")
    with jax.named_scope("mla.attend"):
        # scores over n_nope + n_rope channels, scaled by their root
        attn = blocks.flash(q, k, v, mesh, causal=True, q_rope=q_rope,
                            k_rope=k_rope, scale=scale)
    if c.attn_gate:
        with jax.named_scope("mla.gate"):
            attn = blocks.head_gated(attn, h, p["w_attn_gate"])
    device_profiler.count("mla.layers", 1)  # per lowering
    device_profiler.count("mla.attend_parts", 1)
    return jnp.einsum("bshk,hkd->bsd", attn, p["wo"])


def mla_sublayer(x, p, positions, config, mesh=None,
                 rules: Optional[LogicalAxisRules] = None, rotary=None,
                 scale=None):
    """x [B, S, D] -> x + MLA(RMSNorm(x)) (`mla_mixer`)."""
    h = rms_norm(x, p["attn_norm"], config.norm_eps)
    x = x + mla_mixer(h, p, positions, config, mesh, rotary, scale)
    return blocks.residual(x, mesh, rules)


# --------------------------------------------------------------------------
# KDA
# --------------------------------------------------------------------------
# Kimi Delta Attention (arXiv:2510.26692) through `ops/kda.py`, in the forms
# its two models publish, each a field of the caller's config:
# `kda_lower_bound`: the decay gate. A number b < 0 (Ling's `kda_safe_gate`):
#   g = b x sigmoid(exp(A_log_head) a) in (b, 0); None (Kimi Linear's own,
#   Solar): g = -exp(A_log_head) x softplus(a), ANY value below 0.
# `kda_gate_rank`: 0, a = W_f h + dt_bias and the output gate W_g h at full
#   rank [D, H, d]; r > 0, both through a latent of r (`w_f_down` / `w_g_down`
#   [D, r], then `w_f` / `w_g` [r, H, d]).
# `kda_beta_scale`: beta = scale x sigmoid(w_b . h): 1, or 2 where the model
#   allows I - beta k k^T a negative eigenvalue.

def kda_num_params(c) -> int:
    """The KDA mixer's parameters (no layer norm)."""
    hd = c.n_heads * c.kda_head_dim
    r = c.kda_gate_rank
    gates = 2 * (c.d_model * r + r * hd) if r else 2 * c.d_model * hd
    return (4 * c.d_model * hd + gates + c.d_model * c.n_heads
            + 3 * c.conv_size * hd + c.n_heads + hd + c.kda_head_dim)


def kda_axes(c, L):
    proj = L + ("embed", "heads", "kv")
    gate = L + (None, "heads", "kv") if c.kda_gate_rank else proj
    down = {"w_f_down": L + ("embed", None), "w_g_down": L + ("embed", None)} \
        if c.kda_gate_rank else {}
    return {
        "attn_norm": L + (None,), "wq": proj, "wk": proj, "wv": proj,
        "conv_q": L + (None, "heads", "kv"), "conv_k": L + (None, "heads", "kv"),
        "conv_v": L + (None, "heads", "kv"),
        "w_f": gate, "dt_bias": L + ("heads", "kv"), "a_log": L + ("heads",),
        "w_b": L + ("embed", "heads"), "w_g": gate, **down,
        "o_norm": L + (None,),
        "wo": L + ("heads", "kv", "embed"), "mlp_norm": L + (None,),
    }


def init_kda(config, key):
    """One layer's KDA mixer and its two layer norms. Fan-in scaled normal
    projections; conv taps N(0, 1 / conv_size); `a_log` = log U(1, 16) a
    head (flash-linear-attention's). Under the bounded gate `dt_bias` =
    -U(1, 5) a channel: with a unit-RMS input W_f h is ~N(0, 1), so a head's
    decay a token runs from none (exp(A_log) 16) to ~0.8 (exp(A_log) 1),
    inside (-5, 0) always. Under the softplus gate `dt_bias` is the inverse
    softplus of a step drawn log-uniform in [1e-3, 1e-1]
    (flash-linear-attention's, Mamba-2's rule): g a token is -1e-3 to -1.6
    at a = dt_bias, and where W_f h is +3 and exp(A_log) 16 it is -48:
    no lower bound, and the tail shows at initialisation."""
    c = config
    h, d, r = c.n_heads, c.kda_head_dim, c.kda_gate_rank
    ks = jax.random.split(key, 12)
    proj = lambda k: blocks.dense(c, k, (c.d_model, h, d), c.d_model)  # noqa: E731
    conv = lambda k: blocks.dense(c, k, (c.conv_size, h, d), c.conv_size)  # noqa: E731
    ones = partial(jnp.ones, dtype=c.dtype)
    gate = (lambda k: blocks.dense(c, k, (r, h, d), r)) if r else proj
    down = {name: blocks.dense(c, jax.random.fold_in(key, i),
                               (c.d_model, r), c.d_model)
            for i, name in enumerate(("w_f_down", "w_g_down"))} if r else {}
    if c.kda_lower_bound is None:
        step = jnp.exp(jax.random.uniform(
            ks[7], (h, d), minval=math.log(1e-3), maxval=math.log(1e-1)))
        dt_bias = step + jnp.log(-jnp.expm1(-step))
    else:
        dt_bias = -jax.random.uniform(ks[7], (h, d), minval=1.0, maxval=5.0)
    return {
        "attn_norm": ones((c.d_model,)),
        "wq": proj(ks[0]), "wk": proj(ks[1]), "wv": proj(ks[2]),
        "conv_q": conv(ks[3]), "conv_k": conv(ks[4]), "conv_v": conv(ks[5]),
        "w_f": gate(ks[6]), "dt_bias": dt_bias,
        "a_log": jnp.log(jax.random.uniform(ks[8], (h,), minval=1.0,
                                            maxval=16.0)),
        "w_b": blocks.dense(c, ks[9], (c.d_model, h), c.d_model),
        "w_g": gate(ks[10]), **down, "o_norm": ones((d,)),
        "wo": blocks.dense(c, ks[11], (h, d, c.d_model), h * d),
        "mlp_norm": ones((c.d_model,)),
    }


def _short_conv(x, taps):
    """x [B, S, H, D], taps [K, H, D] -> SiLU of the causal depthwise conv
    over time: y_t = sum_j taps[j] x_{t - (K - 1) + j}, zeros before 0."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0), (0, 0)))
    y = sum(padded[:, j:j + s].astype(jnp.float32)
            * taps[j].astype(jnp.float32) for j in range(k))
    return jax.nn.silu(y)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_operands(xs, taps, dtype):
    """The q, k and v projections' outputs [B, S, H, D] and their convs' taps
    [K, H, D] -> q, k, v [B, H, S, D] in `dtype` as `ops/kda.kda` takes them:
    conv, SiLU, q = l2norm(q) / sqrt(D), k = l2norm(k), rounded, heads
    first. The definition; `ops/kda_prep.prep` is the same in one Pallas call
    each way, where `kda_prep.fused` says it runs."""
    q, k, v = (_short_conv(x, t) for x, t in zip(xs, taps))
    q = _l2norm(q) * xs[0].shape[-1] ** -0.5
    return tuple(jnp.swapaxes(t.astype(dtype), 1, 2)
                 for t in (q, _l2norm(k), v))


def kda_decay(a, dt_bias, a_log, bound):
    """The decay gate's projection a [B, S, H, D] -> g [B, H, S, D] float32,
    the log of a channel's decay a token: -exp(A_log) softplus(a + dt_bias),
    or where the config gives a lower `bound`, bound x sigmoid(exp(A_log) (a
    + dt_bias)). The definition; `ops/kda_prep.gate` is the same in one
    Pallas call each way."""
    a = a.astype(jnp.float32) + dt_bias
    rate = jnp.exp(a_log.astype(jnp.float32))[:, None]
    g = -rate * jax.nn.softplus(a) if bound is None \
        else bound * jax.nn.sigmoid(rate * a)
    return jnp.swapaxes(g, 1, 2)


def kda_sublayer(x, p, config, mesh=None,
                 rules: Optional[LogicalAxisRules] = None):
    """x [B, S, D] -> x + W_o [RMSNorm_head(KDA(q, k, v, g, beta)) x
    sigmoid(gate)] of h = RMSNorm(x): q, k, v = W h, each channel through a
    causal depthwise conv of `conv_size` taps, then SiLU; q = l2norm(q) /
    sqrt(d), k = l2norm(k); g, beta and the gate in the config's forms
    (above), a projection at low rank where the layer has its `_down`
    matrix. No RoPE. `ops/kda.kda` is told what the gate guarantees of g
    (`kda_lower_bound`) and picks its plan from it."""
    c = config
    h = rms_norm(x, p["attn_norm"], c.norm_eps)
    fused = kda_prep.fused(x.shape[:2] + p["wq"].shape[1:], p["conv_q"], mesh)

    def proj(name, rows=False):
        """W h as [B, S, H, D], or as the matmul's own rows [B, S, H D] for
        `ops/kda_prep.py`'s calls, which read a head as a block of lanes (no
        four-dim array whose layout XLA then picks heads first, and copies
        from); a projection at low rank where the layer has its `_down`."""
        w, out = p[name], "hk"
        if rows:
            w, out = w.reshape(w.shape[0], -1), "e"
        if name + "_down" not in p:
            return jnp.einsum(f"bsd,d{out}->bs{out}", h, w)
        with jax.named_scope("kda.gate_lora"):
            return jnp.einsum(f"bsr,r{out}->bs{out}", h @ p[name + "_down"], w)

    heads_first = lambda a: jnp.swapaxes(a, 1, 2)  # noqa: E731
    with jax.named_scope("kda.conv"):
        xs = [proj(w, rows=fused) for w in ("wq", "wk", "wv")]
        taps = [p[t] for t in ("conv_q", "conv_k", "conv_v")]
        q, k, v = (kda_prep.prep if fused else kda_operands)(
            xs, taps, c.dtype)
    rows = 3 * math.prod(q.shape[:3])   # a token's head of q, k or v
    device_profiler.count("kda.prep_rows", rows)  # per lowering
    device_profiler.count("kda.prep_rows_fused", rows if fused else 0)
    with jax.named_scope("kda.gates"):
        g = (kda_prep.gate if fused else kda_decay)(
            proj("w_f", rows=fused), p["dt_bias"], p["a_log"],
            c.kda_lower_bound)
        beta = jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", h, p["w_b"], preferred_element_type=jnp.float32))
        if c.kda_beta_scale != 1.0:
            beta = c.kda_beta_scale * beta
        gate = jax.nn.sigmoid(proj("w_g").astype(jnp.float32))
    with jax.named_scope("kda.scan"):
        o = kda_op.kda(q, k, v, g, heads_first(beta),
                       g_min=c.kda_lower_bound)
    o = rms_norm(heads_first(o), p["o_norm"], c.norm_eps)
    o = (o.astype(jnp.float32) * gate).astype(c.dtype)
    device_profiler.count("kda.layers", 1)  # per lowering
    x = x + jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return blocks.residual(x, mesh, rules)


# --------------------------------------------------------------------------
# Mamba-2, and the layers of kind `M` and `*`
# --------------------------------------------------------------------------

def _conv_silu(x, taps, bias):
    """x [B, S, C], taps [K, C], bias [C] -> SiLU of the causal depthwise
    conv over time: y_t = bias + sum_j taps[j] x_{t - (K - 1) + j}, zeros
    before 0; float32."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + s].astype(jnp.float32)
            * taps[j].astype(jnp.float32) for j in range(k))
    return jax.nn.silu(y + bias.astype(jnp.float32))


def mamba_mixer(h, p, config):
    """Mamba-2 on h [B, S, D], a layer's normed input -> [B, S, D], before
    the residual is added (`granite_hybrid` scales it first):
    [z | xBC | dt] = h W_in, widths H P | H P + 2 G N | H. xBC = SiLU(causal
    depthwise conv over time, `conv_size` taps, with bias), split into x (H
    heads x P), B and C (G groups x N; head j reads group j // (H / G)).
    Delta = softplus(dt + dt_bias) a head, a = -exp(A_log) Delta. State H in
    R^{P x N} a head, float32, H_0 = 0:
        H_t = exp(a_t) H_{t-1} + Delta_t x_t B_t^T,   y_t = H_t C_t + D x_t
    -> [RMSNorm_group(y * SiLU(z))] W_out, the norm over each of the G
    groups' H P / G channels with one scale [H P] (the gate BEFORE the norm).
    `config` gives `mamba_heads`, `mamba_head_dim`, `n_groups`, `state_size`,
    `d_inner`, `conv_dim`, `chunk_size` (the scan's chunk), `norm_eps` and
    `dtype`."""
    c = config
    b, s, _ = h.shape
    heads, groups = c.mamba_heads, c.n_groups
    wide, gn = c.d_inner, c.n_groups * c.state_size
    f32 = jnp.float32
    with jax.named_scope("ssd.project"):
        zxbcdt = h @ p["w_in"]
        z = zxbcdt[..., :wide]
        xbc = _conv_silu(zxbcdt[..., wide:wide + c.conv_dim], p["conv_w"],
                         p["conv_b"]).astype(c.dtype)
        dt = zxbcdt[..., wide + c.conv_dim:]
    with jax.named_scope("ssd.scan"):
        y = ssd_op.ssd(
            xbc[..., :wide].reshape(b, s, heads, c.mamba_head_dim), dt,
            p["a_log"], xbc[..., wide:wide + gn].reshape(
                b, s, groups, c.state_size),
            xbc[..., wide + gn:].reshape(b, s, groups, c.state_size),
            p["d_skip"], p["dt_bias"], chunk=c.chunk_size)
    with jax.named_scope("ssd.gate"):
        gated = (y.reshape(b, s, wide).astype(f32)
                 * jax.nn.silu(z.astype(f32))).reshape(b, s, groups, -1)
        gated = gated * jax.lax.rsqrt(
            jnp.mean(gated * gated, axis=-1, keepdims=True) + c.norm_eps)
        gated = gated.reshape(b, s, wide).astype(c.dtype) * p["gate_norm"]
    device_profiler.count("ssd.layers", 1)  # per lowering
    return gated @ p["w_out"]


def mamba_sublayer(x, p, config, mesh, rules, branch):
    """x [B, S, D] -> x + branch * Mamba-2(RMSNorm(x)); `branch` None: the
    mixer's output as it is (`blocks.scaled`)."""
    h = rms_norm(x, p["norm"], config.norm_eps)
    return blocks.residual(
        x + blocks.scaled(mamba_mixer(h, p, config), branch), mesh, rules)


def mixer_num_params(c, kind: str) -> int:
    """A layer of kind `M` or `*`, less its norm's d_model."""
    d = c.d_model
    if kind == "M":
        return (d * (c.d_inner + c.conv_dim + c.mamba_heads)
                + (c.conv_size + 1) * c.conv_dim + 3 * c.mamba_heads
                + c.d_inner + c.d_inner * d)
    return 2 * d * c.d_head * (c.n_heads + c.n_kv_heads)


def mixer_axes(L, kind: str):
    if kind == "M":
        return {"norm": L + (None,), "w_in": L + ("embed", None),
                "conv_w": L + (None, None), "conv_b": L + (None,),
                "a_log": L + (None,), "d_skip": L + (None,),
                "dt_bias": L + (None,), "gate_norm": L + (None,),
                "w_out": L + (None, "embed")}
    return blocks.attn_axes(L)


def init_mixer(config, kind: str, key):
    """One layer of kind `M` or `*`. Fan-in scaled normal matrices
    (`blocks.dense`), norm scales 1. `M`: conv taps N(0, 1 / conv_size), its
    bias N(0, 0.02^2); `A_log` = log U(1, 16) a head, D = 1, `dt_bias` the
    inverse softplus of a step drawn log-uniform in [`time_step_min`,
    `time_step_max`] and kept above `time_step_floor` (Mamba-2's own
    initialisation): a head's decay a token runs from exp(-0.001) to
    exp(-1.6)."""
    c = config
    d = c.d_model
    ones = partial(jnp.ones, dtype=c.dtype)
    dense = partial(blocks.dense, c)
    ks = jax.random.split(key, 8)
    if kind == "M":
        h = c.mamba_heads
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            ks[2], (h,), minval=math.log(c.time_step_min),
            maxval=math.log(c.time_step_max))), c.time_step_floor)
        return {
            "norm": ones((d,)),
            "w_in": dense(ks[0], (d, c.d_inner + c.conv_dim + h), d),
            "conv_w": dense(ks[1], (c.conv_size, c.conv_dim), c.conv_size),
            "conv_b": (jax.random.normal(ks[5], (c.conv_dim,)) * 0.02).astype(
                c.dtype),
            "a_log": jnp.log(jax.random.uniform(ks[3], (h,), minval=1.0,
                                                maxval=16.0)),
            "d_skip": jnp.ones((h,), jnp.float32),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "gate_norm": ones((c.d_inner,)),
            "w_out": dense(ks[4], (c.d_inner, d), c.d_inner)}
    return {
        "attn_norm": ones((d,)),
        "wq": dense(ks[0], (d, c.n_heads, c.d_head), d),
        "wk": dense(ks[1], (d, c.n_kv_heads, c.d_head), d),
        "wv": dense(ks[2], (d, c.n_kv_heads, c.d_head), d),
        "wo": dense(ks[3], (c.n_heads, c.d_head, d), c.n_heads * c.d_head)}
