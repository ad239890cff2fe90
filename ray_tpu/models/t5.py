"""Encoder-decoder transformer (T5-v1.1-style), TPU-first.

Completes the model-family matrix: decoder-only LLM (llama.py), sparse
MoE (mixtral.py), vision encoder (vit.py), and seq2seq encoder-decoder
here — the architecture behind translation/summarization-class workloads.

Design choices mirror the rest of the zoo: RMSNorm + gated-GELU MLPs
(T5 v1.1), RoPE in the self-attention stacks (cross-attention carries no
positional signal, matching modern enc-dec practice), layers stacked on a
leading axis and scanned so remat/pjit treat depth uniformly, bf16
compute with fp32 logits, and `param_logical_axes` feeding the shared
sharding rules (parallel/sharding.py) for tp/fsdp.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks import (
    embed_rows, ffn_axes, remat_policy, rms_norm, rope)


@dataclasses.dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32_128
    d_model: int = 768
    n_enc_layers: int = 12
    n_dec_layers: int = 12
    n_heads: int = 12
    d_ff: int = 2048
    norm_eps: float = 1e-6
    rope_theta: float = 10_000.0
    pad_id: int = 0
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "dots"

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads

    @staticmethod
    def tiny(vocab_size: int = 512) -> "T5Config":
        return T5Config(vocab_size=vocab_size, d_model=64, n_enc_layers=2,
                        n_dec_layers=2, n_heads=4, d_ff=128,
                        dtype=jnp.float32, remat=False)

    @staticmethod
    def base() -> "T5Config":
        return T5Config()  # t5-v1.1-base shapes

    def num_params(self) -> int:
        d, f = self.d_model, self.d_ff
        attn = 4 * d * d
        mlp = 3 * d * f  # gated
        enc = self.n_enc_layers * (attn + mlp + 2 * d)
        dec = self.n_dec_layers * (2 * attn + mlp + 3 * d)
        return (self.vocab_size * d * 2  # embed + head
                + enc + dec + 2 * d)


def param_logical_axes(config: T5Config) -> Dict[str, Any]:
    """Logical sharding axes per parameter (consumed by
    parallel/sharding.py rules — 'embed' fsdp-shards, 'heads'/'mlp'
    tensor-shard)."""
    E, D = ("enc_layers",), ("dec_layers",)
    attn = lambda L: {  # noqa: E731 — table literal
        "wq": L + ("embed", "heads", "kv"),
        "wk": L + ("embed", "heads", "kv"),
        "wv": L + ("embed", "heads", "kv"),
        "wo": L + ("heads", "kv", "embed"),
    }
    return {
        "embed": ("vocab", "embed"),
        "enc_layers": {
            "ln1": E + (None,), **attn(E),
            "ln2": E + (None,), **ffn_axes(E),
        },
        "dec_layers": {
            "ln1": D + (None,),
            **{f"self_{k}": v for k, v in attn(D).items()},
            "ln2": D + (None,),
            **{f"cross_{k}": v for k, v in attn(D).items()},
            "ln3": D + (None,), **ffn_axes(D),
        },
        "enc_final_ln": (None,),
        "dec_final_ln": (None,),
        "lm_head": ("embed", "vocab"),
    }


def init(config: T5Config, key) -> Dict[str, Any]:
    c = config
    d, h, k_, f = c.d_model, c.n_heads, c.d_head, c.d_ff
    ks = iter(jax.random.split(key, 24))

    def norm(shape, fan_in):
        return (jax.random.normal(next(ks), shape)
                * fan_in ** -0.5).astype(c.dtype)

    def attn(nl, prefix=""):
        return {
            f"{prefix}wq": norm((nl, d, h, k_), d),
            f"{prefix}wk": norm((nl, d, h, k_), d),
            f"{prefix}wv": norm((nl, d, h, k_), d),
            f"{prefix}wo": norm((nl, h, k_, d), h * k_),
        }

    def mlp(nl):
        return {
            "w_gate": norm((nl, d, f), d),
            "w_up": norm((nl, d, f), d),
            "w_down": norm((nl, f, d), f),
        }

    ne, nd = c.n_enc_layers, c.n_dec_layers
    return {
        "embed": norm((c.vocab_size, d), d),
        "enc_layers": {
            "ln1": jnp.ones((ne, d), c.dtype), **attn(ne),
            "ln2": jnp.ones((ne, d), c.dtype), **mlp(ne),
        },
        "dec_layers": {
            "ln1": jnp.ones((nd, d), c.dtype), **attn(nd, "self_"),
            "ln2": jnp.ones((nd, d), c.dtype), **attn(nd, "cross_"),
            "ln3": jnp.ones((nd, d), c.dtype), **mlp(nd),
        },
        "enc_final_ln": jnp.ones((d,), c.dtype),
        "dec_final_ln": jnp.ones((d,), c.dtype),
        "lm_head": norm((d, c.vocab_size), d),
    }


def _heads(x, w):
    return jnp.einsum("bnd,dhk->bnhk", x, w)


def _attend(q, k, v, bias, wo, c: T5Config):
    scores = jnp.einsum("bnhk,bmhk->bhnm", q, k) / (c.d_head ** 0.5)
    scores = scores.astype(jnp.float32) + bias
    attn = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhnm,bmhk->bnhk", attn, v)
    return jnp.einsum("bnhk,hkd->bnd", out, wo)


def _gated_mlp(x, p):
    return (jax.nn.gelu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def _pad_bias(mask):
    """[B, M] keep-mask -> additive [B, 1, 1, M] bias."""
    return jnp.where(mask, 0.0, -1e9)[:, None, None, :].astype(jnp.float32)


def forward_encoder(params, src_tokens, config: T5Config):
    """src_tokens [B, S] int32 -> (enc_hidden [B, S, D], src_mask [B, S])."""
    c = config
    mask = src_tokens != c.pad_id
    bias = _pad_bias(mask)
    x = embed_rows(params["embed"].astype(c.dtype), src_tokens)
    positions = jnp.arange(src_tokens.shape[1])[None, :]

    def layer_fn(x, p):
        h = rms_norm(x, p["ln1"], c.norm_eps)
        q = rope(_heads(h, p["wq"]), positions, c.rope_theta)
        k = rope(_heads(h, p["wk"]), positions, c.rope_theta)
        x = x + _attend(q, k, _heads(h, p["wv"]), bias, p["wo"], c)
        h = rms_norm(x, p["ln2"], c.norm_eps)
        return x + _gated_mlp(h, p)

    if c.remat:
        layer_fn = jax.checkpoint(layer_fn, policy=remat_policy(c))
    x, _ = jax.lax.scan(lambda x, p: (layer_fn(x, p), None), x,
                        params["enc_layers"])
    return rms_norm(x, params["enc_final_ln"], c.norm_eps), mask


def forward_decoder(params, enc_hidden, src_mask, tgt_tokens,
                    config: T5Config):
    """Teacher-forced decoder: tgt_tokens [B, T] -> logits [B, T, V] fp32."""
    c = config
    T = tgt_tokens.shape[1]
    positions = jnp.arange(T)[None, :]
    causal = jnp.where(
        jnp.tril(jnp.ones((T, T), bool)), 0.0, -1e9)[None, None, :, :]
    cross_bias = _pad_bias(src_mask)
    x = embed_rows(params["embed"].astype(c.dtype), tgt_tokens)

    def layer_fn(x, p):
        h = rms_norm(x, p["ln1"], c.norm_eps)
        q = rope(_heads(h, p["self_wq"]), positions, c.rope_theta)
        k = rope(_heads(h, p["self_wk"]), positions, c.rope_theta)
        x = x + _attend(q, k, _heads(h, p["self_wv"]), causal,
                        p["self_wo"], c)
        h = rms_norm(x, p["ln2"], c.norm_eps)
        x = x + _attend(_heads(h, p["cross_wq"]),
                        _heads(enc_hidden, p["cross_wk"]),
                        _heads(enc_hidden, p["cross_wv"]),
                        cross_bias, p["cross_wo"], c)
        h = rms_norm(x, p["ln3"], c.norm_eps)
        return x + _gated_mlp(h, p)

    if c.remat:
        layer_fn = jax.checkpoint(layer_fn, policy=remat_policy(c))
    x, _ = jax.lax.scan(lambda x, p: (layer_fn(x, p), None), x,
                        params["dec_layers"])
    x = rms_norm(x, params["dec_final_ln"], c.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32)


def forward(params, src_tokens, tgt_tokens, config: T5Config):
    enc, src_mask = forward_encoder(params, src_tokens, config)
    return forward_decoder(params, enc, src_mask, tgt_tokens, config)


def loss_fn(params, batch, config: T5Config, mesh=None, rules=None):
    """Seq2seq CE. batch: {"src" [B,S], "tgt" [B,T]} — tgt[:, :-1] feeds
    the decoder, tgt[:, 1:] are labels; pad positions masked out."""
    src, tgt = batch["src"], batch["tgt"]
    logits = forward(params, src, tgt[:, :-1], config)
    labels = tgt[:, 1:]
    mask = (labels != config.pad_id).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def greedy_decode(params, src_tokens, config: T5Config, max_len: int = 32,
                  bos_id: int = 1, eos_id: int = 2):
    """Batched greedy decoding via one jitted teacher-forced step per
    position (test/eval utility; the production path is the inference
    engine's cached decode)."""
    c = config
    enc, src_mask = forward_encoder(params, src_tokens, c)
    B = src_tokens.shape[0]
    tgt = jnp.full((B, max_len), c.pad_id, jnp.int32)
    tgt = tgt.at[:, 0].set(bos_id)
    step = jax.jit(
        lambda p, e, m, t: forward_decoder(p, e, m, t, c).argmax(-1))
    done = jnp.zeros((B,), bool)
    for i in range(1, max_len):
        nxt = step(params, enc, src_mask, tgt)[:, i - 1]
        nxt = jnp.where(done, c.pad_id, nxt)
        tgt = tgt.at[:, i].set(nxt)
        done = done | (nxt == eos_id)
        if bool(done.all()):
            break
    return tgt
