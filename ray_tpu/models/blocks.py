"""The layer library: what every model module of `ray_tpu/models/` shares,
under public names. A model module imports from here (and from `experts.py`,
`mixers.py`, `layer_pattern.py`), never from another model module.

Norms and rotary embeddings; the attention sublayer and its flash entry; the
dense MLP (over `tp`, a ring with every transfer under a matmul); the
residual stream's layout between sublayers; remat; the two ends of a decoder
(`embed_tokens`, `chunked_ce`, `next_token_loss`); initialisation. A block
reads its widths off its parameters' shapes and the rest off its `config`'s
fields (`norm_eps`, `rope_theta`, `dtype`, `remat_policy`, ...), whichever
model's it is; what differs by the KIND of a layer is an argument (`mask`,
`rotary`, `scale`, `branch`), not a field.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import device_profiler
from ray_tpu.ops import row_sums
from ray_tpu.ops.flash_attention import RESIDUAL_NAMES, flash_attention
from ray_tpu.parallel.sharding import (
    LogicalAxisRules,
    logical_sharding,
    with_logical_constraint,
)


def remat_policy(config):
    """Map config.remat_policy to a jax.checkpoint policy (None = full)."""
    name = getattr(config, "remat_policy", "full")
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name == "residuals":
        # nothing of the layer's own: with `checkpointed`, which
        # adds the kernels' named residuals, a layer keeps its input, the
        # flash call's o and lse and the scan's y and recomputes the rest
        # (a long sequence: "dots" would keep ~1.9 GB a layer of
        # `granite_hybrid` at S 32,768)
        return jax.checkpoint_policies.nothing_saveable
    if name != "full":
        raise ValueError(
            f"remat_policy {name!r}: \"full\" (recompute everything), "
            "\"dots\" (save matmul outputs) or \"residuals\" (the "
            "kernels' named residuals alone)")
    return None


def checkpointed(fn, config, names=RESIDUAL_NAMES):
    """`fn` under `config.remat_policy` and, whatever that saves, the
    kernels' own residuals `names` beside it (the flash call's output and
    lse, named by `ops/flash_attention.py`: 65 MiB a layer at B 4 x S 2048;
    a model adds its mixer's, `ops/kda.py`'s or `ops/ssd.py`'s), so the
    backward pass runs no second forward kernel. "full" saves nothing."""
    if not config.remat:
        return fn
    policy = remat_policy(config)
    if policy is not None:
        policy = jax.checkpoint_policies.save_from_both_policies(
            policy, jax.checkpoint_policies.save_only_these_names(*names))
    return jax.checkpoint(fn, policy=policy)


def rms_norm(x, weight, eps):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dtype) * weight


def qk_norm(q, k, params, config):
    """With `config.qk_norm`, before RoPE, by the SHAPE of the scale: `q_norm`
    [H, D] is one RMSNorm over ALL channels of the q projection [B, S, H, D]
    and one over all of the k projection, not per head (OLMoE); `q_norm` [D]
    is an RMSNorm of every head over its own D channels, one scale for all q
    heads and one for all kv heads (Qwen3). Otherwise q and k as given."""
    if not config.qk_norm:
        return q, k

    def norm(x, weight):
        if weight.ndim == 1:
            return rms_norm(x, weight, config.norm_eps)
        b, s, h, d = x.shape
        return rms_norm(x.reshape(b, s, h * d), weight.reshape(h * d),
                         config.norm_eps).reshape(b, s, h, d)

    return norm(q, params["q_norm"]), norm(k, params["k_norm"])


class Rotary(NamedTuple):
    """The rotary form of ONE KIND of attention layer, where a model has
    several (`models/window_moe.py`): base `theta` (0: no rotary embedding);
    `width`, the LEADING channels of a head that rotate, in pairs (d, d +
    width / 2), the rest passing through (None: the whole head); `yarn`,
    (factor, original_max_position, beta_fast, beta_slow), or None for the
    plain frequencies; `attention_factor` multiplies cos and sin, so the
    rotated channels of q and k carry it and the others do not."""
    theta: float
    width: Optional[int] = None
    yarn: Optional[tuple] = None
    attention_factor: float = 1.0

    def inv_freq(self, d_head: int):
        """float32 [width / 2]: theta ** (-2i / width), under `yarn`
        blended as HF's `_compute_yarn_parameters` blends them: a pair that
        turns more than `beta_fast` times over the original context keeps
        its frequency, one that turns less than `beta_slow` times has it
        divided by `factor`, a linear ramp over the pairs between."""
        width = self.width or d_head
        plain = self.theta ** (-np.arange(0, width, 2, dtype=np.float64)
                               / width)
        if self.yarn is None:
            return jnp.asarray(plain, jnp.float32)
        factor, original, beta_fast, beta_slow = self.yarn

        def pair_turning(times):
            return width * math.log(original / (times * 2 * math.pi)) \
                / (2 * math.log(self.theta))

        low = max(math.floor(pair_turning(beta_fast)), 0)
        high = min(math.ceil(pair_turning(beta_slow)), width - 1)
        ramp = np.clip((np.arange(width // 2) - low)
                       / max(high - low, 0.001), 0, 1)
        return jnp.asarray(plain * (1 - ramp) + plain / factor * ramp,
                           jnp.float32)


def rope(x, positions, theta, rotary: Optional[Rotary] = None):
    # x: [B, S, H, D]; rotate pairs (d, d + D/2); under `rotary`, of its
    # leading `width` channels, at its frequencies, cos and sin scaled.
    d = x.shape[-1]
    if rotary is None:
        half = d // 2
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    else:
        freqs = rotary.inv_freq(d)
        half = freqs.shape[0]
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, S, 1, half]
    sin = jnp.sin(angles)[:, :, None, :]
    if rotary is not None and rotary.attention_factor != 1.0:
        cos, sin = cos * rotary.attention_factor, sin * rotary.attention_factor
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if 2 * half < d:
        turned.append(x[..., 2 * half:].astype(jnp.float32))
    return jnp.concatenate(turned, axis=-1).astype(x.dtype)


def attention(q, k, v, config, mesh=None, mask=None,
               scale=None):
    """Causal flash attention, or under `mask` (a static rule of
    `ops/flash_attention.py`) in its scope, which names the Pallas events;
    scores times `scale` where the caller gives one (None: the kernels'
    d_head ** -0.5)."""
    if mask is not None:
        if config.use_ring_attention:
            raise NotImplementedError("ring attention is causal only")
        with jax.named_scope(mask.scope):
            return flash(q, k, v, mesh, mask=mask, scale=scale)
    if config.use_ring_attention and mesh is not None and mesh.shape.get("sp", 1) > 1:
        from ray_tpu.parallel.ring_attention import ring_attention_sharded

        if scale is not None:
            raise NotImplementedError("ring attention scales by d_head ** -0.5")
        rep = config.n_heads // config.n_kv_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return ring_attention_sharded(q, k, v, mesh, causal=True)
    return flash(q, k, v, mesh, causal=True, scale=scale)


def flash(q, k, v, mesh, **rule):
    """`flash_attention` under the static rule `rule` (its keywords), each
    chip on its own rows where the mesh shards the batch or the heads."""
    if mesh is not None and any(
        mesh.shape.get(a, 1) > 1 for a in ("dp", "fsdp", "tp")
    ):
        from ray_tpu.ops.flash_attention import flash_attention_sharded

        return flash_attention_sharded(q, k, v, mesh, **rule)
    return flash_attention(q, k, v, **rule)


def qkv(x, params, positions, config, lc=None, rotary=None):
    """The attention prologue every sublayer shares: pre-norm, the q/k/v
    projections (as many heads as the layer's `wq` has), QK-norm, RoPE on q
    and k: at `config.rope_theta` (none where it is 0), or in the form
    `rotary` of the layer's kind (`Rotary`). `lc` (training only)
    constrains q and k to their logical layout between the norm and RoPE.
    -> q [B,S,H,K], k and v [B,S,kv,K]."""
    c = config
    h = rms_norm(x, params["attn_norm"], c.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, params["wv"])
    q, k = qk_norm(q, k, params, c)
    if lc is not None:
        q = lc(q, ("batch", "seq", "act_heads", "act_kv"))
        k = lc(k, ("batch", "seq", "act_heads", "act_kv"))
    theta = c.rope_theta if rotary is None else rotary.theta
    if theta:  # 0: no rotary embedding (`models/nemotron_h.py`)
        q = rope(q, positions, theta, rotary)
        k = rope(k, positions, theta, rotary)
    return q, k, v


# The residual stream [B, S, D] BETWEEN sublayers: the sequence dim over
# `sp` and `tp` ("res_seq"). On a tp mesh the sum over tp that ends a
# row-parallel matmul (wo, w_down) then lands as a reduce-scatter, norms and
# residual adds run on S / tp rows a chip, and the next column-parallel
# matmul (q/k/v, gate/up) gathers the rows it needs. Where tp does not
# divide S (decode, S = 1) or is 1 this is `("batch", "seq", "act_embed")`.
_RESIDUAL = ("batch", "res_seq", "act_embed")


def _residual_seq_axes(x, mesh, rules) -> tuple:
    """The mesh axes that x's sequence dim is scattered over between
    sublayers, on a mesh handed in by the caller (the training step); ()
    without one (the paged forward's ambient mesh is the compiler's)."""
    if mesh is None:
        return ()
    axes = logical_sharding(mesh, _RESIDUAL, rules, x.shape[-3:]).spec[1]
    return axes if isinstance(axes, tuple) else (axes,) if axes else ()


def residual(x, mesh=None, rules: Optional[LogicalAxisRules] = None):
    """Hold the residual stream to its layout between sublayers: x
    [B, S, D], or n streams of it [n, B, S, D] (`models/streams.py`), the
    streams' dim whole on every chip."""
    if "tp" in _residual_seq_axes(x, mesh, rules):
        # per LOWERING of a boundary, not per run (the scanned layer body
        # lowers once for all layers)
        device_profiler.count("tp.seq_sharded_boundaries")
    axes = _RESIDUAL if x.ndim == 3 else (None,) + _RESIDUAL
    return with_logical_constraint(x, axes, mesh=mesh, rules=rules)


def mlp_ring(h, params, mesh):
    """silu(h w_gate) * (h w_up) w_down over tp with every transfer under a
    matmul. h [B, S, D] arrives with S scattered over tp and the result
    leaves so; gate/up are column-parallel, w_down row-parallel. Left to the
    compiler, the all-gather of h and the reduce-scatter of the output stand
    alone on the device's op line, 1.44 and 1.81 ms a layer each way at
    train-4chip's shapes (PERF.md §6, PR 30). Here the chips pass their row
    chunks round a ring (`ppermute`, an async collective-permute) while they
    multiply the chunk they hold, then pass the partial sums of w_down's
    output round it while they multiply the next chunk: position t of `ffs`
    holds chunk (i + t) % n on chip i, so no index depends on the chip.
    Manual over tp only; batch and fsdp stay the compiler's."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape["tp"]
    to_previous = [(i, (i - 1) % n) for i in range(n)]

    def ring(h, w_gate, w_up, w_down):
        ffs = []
        for t in range(n):
            arriving = (jax.lax.ppermute(h, "tp", to_previous)
                        if t < n - 1 else None)
            ffs.append(jax.nn.silu(jnp.einsum("bsd,df->bsf", h, w_gate))
                       * jnp.einsum("bsd,df->bsf", h, w_up))
            h = arriving
        # chip i sums chunk (i + 1 + t) % n at step t, its own one last
        out = None
        for t in range(n):
            part = jnp.einsum("bsf,fd->bsd", ffs[(t + 1) % n], w_down)
            if out is not None:
                # the barrier keeps the sum out of the matmul's fusion,
                # where it would make the matmul wait for the transfer
                part, arrived = jax.lax.optimization_barrier(
                    (part, jax.lax.ppermute(out, "tp", to_previous)))
                part = part + arrived
            out = part
        return out

    return jax.shard_map(
        ring, mesh=mesh, axis_names={"tp"},
        in_specs=(P(None, "tp", None), P(None, "tp"), P(None, "tp"),
                  P("tp", None)),
        out_specs=P(None, "tp", None),
    )(h, params["w_gate"], params["w_up"], params["w_down"])


def head_gated(attn, h, w_gate):
    """attn [B, S, H, K] * sigmoid(w_head . h) a head: h [B, S, D] the
    layer's normed input, w_gate [D, H]; the gate in float32."""
    return attn * jax.nn.sigmoid(jnp.einsum(
        "bsd,dh->bsh", h, w_gate,
        preferred_element_type=jnp.float32))[..., None].astype(attn.dtype)


def channel_gated(attn, h, w_gate):
    """`head_gated`'s sibling, a gate a CHANNEL: attn [B, S, H, K] *
    sigmoid(W_gate h), w_gate [D, H, K] (arXiv:2505.06708's elementwise
    form); the gate in float32."""
    return attn * jax.nn.sigmoid(jnp.einsum(
        "bsd,dhk->bshk", h, w_gate,
        preferred_element_type=jnp.float32)).astype(attn.dtype)


def attn_sublayer(x, params, positions, config, mesh=None,
                   rules: Optional[LogicalAxisRules] = None, mask=None,
                   rotary=None, scale=None, branch=None):
    """Pre-norm attention block of a training layer:
    causal, or under the static rule `mask` (`attention`). What may differ
    by the KIND of a layer comes from the caller, not from `config`: the
    rule, the rotary form (`rotary`), the number of query heads (the
    layer's `wq`) and a gate on the output before `wo` where the layer has
    a `w_attn_gate`, by ITS shape: [D, H] a gate per head, attn_head *
    sigmoid(w_head . h) (`mixers.mla_sublayer`'s form), [D, H, K] a gate
    per channel (`channel_gated`), the scores' `scale` where it
    is not d_head ** -0.5 and `branch`, a multiplier on what the block adds
    to the residual (`models/granite_hybrid.py`'s published two)."""
    lc = partial(with_logical_constraint, mesh=mesh, rules=rules)
    q, k, v = qkv(x, params, positions, config, lc, rotary)
    if "tp" in _residual_seq_axes(x, mesh, rules):
        # v too leaves its projection with heads over tp, from the rows
        # gathered for q and k: left unsaid, the compiler projects the local
        # rows onto every head and turns v round with an all-to-all
        v = lc(v, ("batch", "seq", "act_heads", "act_kv"))
    attn = attention(q, k, v, config, mesh, mask, scale)
    if "w_attn_gate" in params:
        with jax.named_scope("attn.gate"):
            # `qkv`'s normed input: the compiler keeps one
            h = rms_norm(x, params["attn_norm"], config.norm_eps)
            w_gate = params["w_attn_gate"]
            attn = (head_gated if w_gate.ndim == 2 else channel_gated)(
                attn, h, w_gate)
    x = x + scaled(jnp.einsum("bshk,hkd->bsd", attn, params["wo"]), branch)
    return residual(x, mesh, rules)


def scaled(out, branch):
    """A block's output times its residual multiplier, the product formed
    in float32 and rounded once; None: as it is."""
    if branch is None:
        return out
    return (out.astype(jnp.float32) * branch).astype(out.dtype)


def gated_mlp(h, params, config, mesh=None,
              rules: Optional[LogicalAxisRules] = None):
    """The SwiGLU MLP of h [B, S, D], a layer's normed input, before the
    residual is added (`mlp_sublayer` adds it; `models/streams.py` spreads
    it over the streams): the ring over `tp` where the rows arrive
    scattered over it, `swiglu` elsewhere."""
    lc = partial(with_logical_constraint, mesh=mesh, rules=rules)
    if _residual_seq_axes(h, mesh, rules) == ("tp",) \
            and config.d_ff % mesh.shape["tp"] == 0:
        return mlp_ring(h, params, mesh)
    return swiglu(h, params, lc)


def mlp_sublayer(x, params, config, mesh=None,
                  rules: Optional[LogicalAxisRules] = None, branch=None):
    """Pre-norm SwiGLU MLP block shared by training and decode paths;
    `branch` as `attn_sublayer`'s."""
    h = rms_norm(x, params["mlp_norm"], config.norm_eps)
    return residual(
        x + scaled(gated_mlp(h, params, config, mesh, rules), branch),
        mesh, rules)


def swiglu(h, params, lc):
    """W_down (silu(W_gate h) * W_up h), h [B, S, D] the normed input; `lc`
    constrains the gate to its logical layout."""
    gate = jnp.einsum("bsd,df->bsf", h, params["w_gate"])
    up = jnp.einsum("bsd,df->bsf", h, params["w_up"])
    gate = lc(gate, ("batch", "seq", "act_mlp"))
    ff = jax.nn.silu(gate) * up
    return jnp.einsum("bsf,fd->bsd", ff, params["w_down"])


def dense(config, key, shape, fan_in):
    """A normal draw of `shape`, float32 times fan_in ** -0.5, in the
    config's dtype."""
    return (jax.random.normal(key, shape, dtype=jnp.float32)
            * (fan_in ** -0.5)).astype(config.dtype)


def init_ffn(config, keys, lead, width):
    """A gated MLP's three matrices at `width`, stacked under `lead`."""
    c = config
    return {"w_gate": dense(c, keys[0], lead + (c.d_model, width), c.d_model),
            "w_up": dense(c, keys[1], lead + (c.d_model, width), c.d_model),
            "w_down": dense(c, keys[2], lead + (width, c.d_model), width)}


def attn_axes(L):
    """`attn_sublayer`'s norm and four projections under the leading axes
    `L`."""
    proj = L + ("embed", "heads", "kv")
    return {"attn_norm": L + (None,), "wq": proj, "wk": proj, "wv": proj,
            "wo": L + ("heads", "kv", "embed")}


def ffn_axes(L):
    """`init_ffn`'s logical axes under the leading axes `L`."""
    return {"w_gate": L + ("embed", "mlp"), "w_up": L + ("embed", "mlp"),
            "w_down": L + ("mlp", "embed")}


def embed_rows(table, tokens, mesh=None):
    """table [V, D], tokens [...] in [0, V) -> [..., D]: `table[tokens]`,
    the same gather, with a backward rule of its own. Autodiff transposes
    the gather into a scatter-add of one row after the other into a zero
    table: 0.12-0.34 us a row on the v5e where D is 2,048 or 4,096, 1 us
    where it is 2,560. d table is the sum of the cotangent's rows by their
    token, and `ops/row_sums.sum_rows_by_index` forms it with no scatter (one
    sort, one gather, one pass of the MXU; float32 sums rounded once) on a
    TPU, for a bf16 cotangent whose rows the scatter-add pays 1 us for
    (`row_sums.sums_by_index_in_order`), where the step's mesh (`mesh`, or
    the ambient one) is absent or has one device. Everywhere else the
    scatter-add stands: over more devices the table arrives sharded by
    `vocab` and the tokens by the batch, and a Pallas call outside
    `shard_map` is not the partitioner's to split. Counted as the backward
    rule is traced: `embed.grad_rows`, and of them `embed.grad_rows_sorted`,
    the rows the sorted sum adds up."""
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    return _embed_rows(table, tokens, mesh.size <= 1)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _embed_rows(table, tokens, one_device):
    return table[tokens]


def _embed_rows_fwd(table, tokens, one_device):
    return table[tokens], (tokens, table.shape[0])


def _embed_rows_bwd(one_device, held, d):
    tokens, v = held
    sort = one_device and row_sums.sums_by_index_in_order(
        d.dtype, d.shape[-1])
    device_profiler.count("embed.grad_rows", tokens.size)
    device_profiler.count("embed.grad_rows_sorted", tokens.size * sort)
    if sort:
        return row_sums.sum_rows_by_index(
            d.reshape(tokens.size, -1), tokens.reshape(-1), v), None
    return jnp.zeros((v, d.shape[-1]), d.dtype).at[tokens].add(d), None


_embed_rows.defvjp(_embed_rows_fwd, _embed_rows_bwd)


def embed_tokens(params, tokens, mesh=None,
                 rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> (their rows of `params["embed"]` [B, S, D] in the
    table's dtype, positions [B, S]: 0..S-1 a row). The cast to the
    stream's dtype, a multiplier and `residual` are the caller's next line.

    The table's embed dim is constrained to the ACTIVATION layout
    (replicated) before the lookup: a gather from an fsdp-sharded embed dim
    makes the output D-sharded, and XLA can only reach the (batch, seq,
    None) activation layout from there via involuntary full
    rematerialization (replicate-then-repartition). With embed replicated
    at the gather the reshard to the activation spec is a local slice."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    table = with_logical_constraint(params["embed"], ("vocab", "act_embed"),
                                    mesh=mesh, rules=rules)
    return embed_rows(table, tokens, mesh), positions


def chunked_ce(hidden, lm_head, targets, mask=None, chunk: int = 256,
               denominator=None, groups: int = 1):
    """Cross-entropy without materializing full [B,S,V] fp32 logits: the
    sequence is scanned in chunks of `chunk` positions (what is left over
    takes the same body after the scan). `mask` [B,S] weights each
    position's term (0/1, or any float32 weight); the sum is divided by the
    mask's sum, or by `denominator` when the weights are no count.

    `groups` > 1: `lm_head` [D, groups x V] is several heads side by side
    (prediction heads over one vocabulary), the softmax runs over each
    V-wide group of its columns, and `targets` and `mask` are [B, S, groups],
    each group's own target and weight: ONE matmul a chunk against the whole
    head, not one a group. A term's weight is `mask` over the denominator,
    whichever group it is in.

    Differentiated, a chunk's logits are formed ONCE: the forward pass
    takes, beside each chunk's loss, the gradient of the whole loss by that
    chunk's logits, (softmax - onehot) * weight / denominator in float32
    rounded to the logits' dtype, and multiplies it out while the logits are
    there. So the forward holds d loss / d hidden `[B, S, D]` and
    d loss / d lm_head `[D, V]` for a cotangent of 1, and the backward pass
    only scales the two by the cotangent it is given (in float32, rounded
    once). d lm_head accumulates over the chunks in `lm_head`'s dtype, as
    the transposed scan's carry did: in float32 every chunk would read and
    write a second `[D, V]` array twice as wide. `targets`, `mask` and
    `denominator` are data and get no cotangent.

    Not differentiated (an evaluation, a reference check), only the loss is
    formed. Counted as traced: `ce.chunks`, and of them `ce.chunks_fused`,
    the chunks whose gradient is formed with their logits; `ce.groups`, the
    groups of a call's head."""
    b, s, _ = hidden.shape
    if targets.shape != ((b, s) if groups == 1 else (b, s, groups)) \
            or lm_head.shape[1] % groups:
        raise ValueError(
            f"targets {targets.shape} and a head {lm_head.shape} in "
            f"{groups} groups for hidden states {hidden.shape}")
    if mask is None:
        mask = jnp.ones(targets.shape, jnp.float32)
    if denominator is None:
        denominator = jnp.maximum(jnp.sum(mask), 1.0)
    device_profiler.count("ce.groups", groups)
    return _chunked_ce(hidden, lm_head, targets, mask,
                       jnp.asarray(denominator, jnp.float32), chunk)


def _ce_chunks(chunk, hidden, targets, mask, fused):
    """([n, B, chunk, ...] stacks of the whole chunks, the remainder's
    [B, rem, ...] or None where the chunk divides S) of hidden, targets and
    mask; and the chunks counted."""
    b, s, _ = hidden.shape
    n = s // chunk
    main = tuple(
        jnp.moveaxis(a[:, :n * chunk].reshape(b, n, chunk, *a.shape[2:]), 1, 0)
        for a in (hidden, targets, mask))
    rest = tuple(a[:, n * chunk:] for a in (hidden, targets, mask)) \
        if s > n * chunk else None
    chunks = n + (rest is not None)
    device_profiler.count("ce.chunks", chunks)
    device_profiler.count("ce.chunks_fused", chunks * fused)
    return main, rest


def _ce_chunk(lm_head, h_ck, t_ck, m_ck):
    """A chunk's float32 log-probabilities [B, chunk, V] (in groups, `t_ck`
    [B, chunk, groups]: [B, chunk, groups, V / groups], each group's own
    softmax), where its targets stand in them, and its weighted sum of
    -log p(target). The target's term is picked by comparison, not gathered:
    a gather has the compiler write the float32 [B, chunk, V] array to HBM to
    read one element a row."""
    logits = (h_ck @ lm_head).astype(jnp.float32)
    if t_ck.ndim == 3:
        logits = logits.reshape(*t_ck.shape, -1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    hit = t_ck[..., None] == jnp.arange(logp.shape[-1])
    nll = -jnp.sum(jnp.where(hit, logp, 0.0), axis=-1)
    return logp, hit, jnp.sum(nll * m_ck)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _chunked_ce(hidden, lm_head, targets, mask, denominator, chunk):
    def body(total, xs):
        return total + _ce_chunk(lm_head, *xs)[2], None

    main, rest = _ce_chunks(chunk, hidden, targets, mask, fused=False)
    total, _ = jax.lax.scan(body, jnp.float32(0.0), main)
    if rest is not None:
        total, _ = body(total, rest)
    return total / denominator


def _chunked_ce_fwd(hidden, lm_head, targets, mask, denominator, chunk):
    def body(carry, xs):
        total, dw = carry
        h_ck, t_ck, m_ck = xs
        logp, hit, nll = _ce_chunk(lm_head, h_ck, t_ck, m_ck)
        dlogits = ((jnp.exp(logp) - hit) * (m_ck / denominator)[..., None]
                   ).astype(jnp.result_type(h_ck, lm_head))
        if t_ck.ndim == 3:  # the groups side by side again
            dlogits = dlogits.reshape(*h_ck.shape[:2], -1)
        dh_ck = jnp.einsum("bcv,dv->bcd", dlogits, lm_head)
        dw = dw + jnp.einsum("bcd,bcv->dv", h_ck, dlogits).astype(dw.dtype)
        return (total + nll, dw), dh_ck.astype(h_ck.dtype)

    main, rest = _ce_chunks(chunk, hidden, targets, mask, fused=True)
    (total, dw), dh = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.zeros_like(lm_head)), main)
    b, _, d = hidden.shape
    dh = jnp.moveaxis(dh, 0, 1).reshape(b, -1, d)
    if rest is not None:
        (total, dw), dh_rest = body((total, dw), rest)
        dh = jnp.concatenate([dh, dh_rest], axis=1)
    return total / denominator, (dh, dw)


def _chunked_ce_bwd(chunk, held, g):
    return tuple((g * x.astype(jnp.float32)).astype(x.dtype)
                 for x in held) + (None, None, None)


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)



def split_batch(batch):
    """{"tokens": [B, S + 1]} or {"inputs", "targets"[, "mask"]} ->
    (inputs, targets, mask or None): the targets are the tokens shifted."""
    if "inputs" in batch:
        return batch["inputs"], batch["targets"], batch.get("mask")
    tokens = batch["tokens"]
    return tokens[:, :-1], tokens[:, 1:], None


def next_token_loss(forward_hidden, mtp_hidden, params, batch, config,
                    mesh=None, rules: Optional[LogicalAxisRules] = None):
    """Next-token CE of a decoder whose `forward_hidden(params, inputs,
    config, mesh, rules)` gives (final-norm hidden states, the chosen
    experts), through `chunked_ce` on `params["lm_head"]` in chunks of
    `loss_chunk_size` (0: one chunk), masked by batch["mask"] when given;
    + `mtp_loss_coef` * the same CE of the model's MTP block where it has
    one (`mtp_hidden(params, hidden, targets, config, mesh, rules)`, not
    None, and `mtp_depth`), predicting the token after the next through
    the shared head (`mtp_targets`). Scalar (make_train_step contract)."""
    c = config
    inputs, targets, mask = split_batch(batch)
    chunk = c.loss_chunk_size or inputs.shape[1]
    hidden, _ = forward_hidden(params, inputs, c, mesh, rules)
    loss = chunked_ce(hidden, params["lm_head"], targets, mask, chunk=chunk)
    if mtp_hidden is not None and c.mtp_depth:
        h_mtp, _ = mtp_hidden(params, hidden, targets, c, mesh, rules)
        loss = loss + c.mtp_loss_coef * chunked_ce(
            h_mtp, params["lm_head"], *mtp_targets(targets, mask),
            chunk=chunk)
    return loss


def mtp_targets(targets, mask=None):
    """targets [B, S] (t_{i+1} at position i) -> (t_{i+2} [B, S], mask
    [B, S] float32): the targets one further on; the last position has none
    and is masked (its id is 0, never read)."""
    b, s = targets.shape
    shifted = jnp.concatenate(
        [targets[:, 1:], jnp.zeros((b, 1), targets.dtype)], axis=1)
    last = (jnp.arange(s) < s - 1).astype(jnp.float32)[None]
    return shifted, last * (jnp.ones((b, s)) if mask is None else mask)
