"""Vision Transformer (encoder), TPU-first.

Completes the model-family coverage (decoder LLM: llama.py, sparse MoE:
mixtral.py, vision encoder: here). Bidirectional attention over patch
embeddings; shapes kept MXU-friendly (patchify = one reshape + matmul);
layers stacked and scanned like the LLM stack so remat/pjit treat the
depth dimension uniformly.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models.blocks import remat_policy


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_channels: int = 3
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    num_classes: int = 1000
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "full" recomputes everything; "dots" saves matmul outputs and
    # recomputes only cheap elementwise ops (~6% faster at 500M/1-chip,
    # still fits long-seq activations in HBM).
    remat_policy: str = "dots"

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads

    @staticmethod
    def tiny() -> "ViTConfig":
        return ViTConfig(image_size=32, patch_size=8, d_model=64, n_layers=2,
                         n_heads=4, d_ff=128, num_classes=10,
                         dtype=jnp.float32, remat=False)

    @staticmethod
    def base_16() -> "ViTConfig":
        return ViTConfig()  # ViT-B/16

    def num_params(self) -> int:
        patch_dim = self.patch_size ** 2 * self.num_channels
        per_layer = (4 * self.d_model * self.d_model
                     + 2 * self.d_model * self.d_ff
                     + 5 * self.d_model + self.d_ff)  # 4 LN vecs + b1 + b2
        return (patch_dim * self.d_model + self.d_model  # patch proj
                + (self.n_patches + 1) * self.d_model    # pos emb (+cls)
                + self.d_model                           # cls token
                + self.n_layers * per_layer
                + 2 * self.d_model
                + self.d_model * self.num_classes + self.num_classes)


def param_logical_axes(config: ViTConfig) -> Dict[str, Any]:
    L = ("layers",)
    return {
        "patch_proj": ("patch", "embed"),
        "patch_bias": ("embed",),
        "pos_embed": (None, "embed"),
        "cls_token": ("embed",),
        "layers": {
            "ln1_scale": L + (None,), "ln1_bias": L + (None,),
            "wq": L + ("embed", "heads", "kv"),
            "wk": L + ("embed", "heads", "kv"),
            "wv": L + ("embed", "heads", "kv"),
            "wo": L + ("heads", "kv", "embed"),
            "ln2_scale": L + (None,), "ln2_bias": L + (None,),
            "w1": L + ("embed", "mlp"), "b1": L + ("mlp",),
            "w2": L + ("mlp", "embed"), "b2": L + (None,),
        },
        "final_ln_scale": (None,), "final_ln_bias": (None,),
        "head_w": ("embed", "vocab"), "head_b": ("vocab",),
    }


def init(config: ViTConfig, key) -> Dict[str, Any]:
    c = config
    ks = jax.random.split(key, 12)
    patch_dim = c.patch_size ** 2 * c.num_channels
    d, h, k_, f, nl = c.d_model, c.n_heads, c.d_head, c.d_ff, c.n_layers

    def norm(key, shape, scale):
        return (jax.random.normal(key, shape) * scale).astype(c.dtype)

    return {
        "patch_proj": norm(ks[0], (patch_dim, d), patch_dim ** -0.5),
        "patch_bias": jnp.zeros((d,), c.dtype),
        "pos_embed": norm(ks[1], (c.n_patches + 1, d), 0.02),
        "cls_token": norm(ks[2], (d,), 0.02),
        "layers": {
            "ln1_scale": jnp.ones((nl, d), c.dtype),
            "ln1_bias": jnp.zeros((nl, d), c.dtype),
            "wq": norm(ks[3], (nl, d, h, k_), d ** -0.5),
            "wk": norm(ks[4], (nl, d, h, k_), d ** -0.5),
            "wv": norm(ks[5], (nl, d, h, k_), d ** -0.5),
            "wo": norm(ks[6], (nl, h, k_, d), (h * k_) ** -0.5),
            "ln2_scale": jnp.ones((nl, d), c.dtype),
            "ln2_bias": jnp.zeros((nl, d), c.dtype),
            "w1": norm(ks[7], (nl, d, f), d ** -0.5),
            "b1": jnp.zeros((nl, f), c.dtype),
            "w2": norm(ks[8], (nl, f, d), f ** -0.5),
            "b2": jnp.zeros((nl, d), c.dtype),
        },
        "final_ln_scale": jnp.ones((d,), c.dtype),
        "final_ln_bias": jnp.zeros((d,), c.dtype),
        "head_w": norm(ks[9], (d, c.num_classes), d ** -0.5),
        "head_b": jnp.zeros((c.num_classes,), c.dtype),
    }


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def patchify(images, config: ViTConfig):
    """[B, H, W, C] -> [B, N, patch_dim] with one reshape/transpose chain."""
    c = config
    b, hh, ww, ch = images.shape
    p = c.patch_size
    x = images.reshape(b, hh // p, p, ww // p, p, ch)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (hh // p) * (ww // p), p * p * ch)


def forward(params, images, config: ViTConfig):
    """images [B,H,W,C] float -> logits [B, num_classes] fp32."""
    c = config
    x = patchify(images.astype(c.dtype), c) @ params["patch_proj"]
    x = x + params["patch_bias"]
    b = x.shape[0]
    cls = jnp.broadcast_to(params["cls_token"], (b, 1, c.d_model))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"]

    def layer_fn(x, p):
        h = _ln(x, p["ln1_scale"], p["ln1_bias"], c.norm_eps)
        q = jnp.einsum("bnd,dhk->bnhk", h, p["wq"])
        k = jnp.einsum("bnd,dhk->bnhk", h, p["wk"])
        v = jnp.einsum("bnd,dhk->bnhk", h, p["wv"])
        scores = jnp.einsum("bnhk,bmhk->bhnm", q, k) / (c.d_head ** 0.5)
        attn = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        out = jnp.einsum("bhnm,bmhk->bnhk", attn.astype(v.dtype), v)
        x = x + jnp.einsum("bnhk,hkd->bnd", out, p["wo"])
        h = _ln(x, p["ln2_scale"], p["ln2_bias"], c.norm_eps)
        ff = jax.nn.gelu(h @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
        return x + ff

    if c.remat:
        layer_fn = jax.checkpoint(layer_fn, policy=remat_policy(c))
    x, _ = jax.lax.scan(lambda x, p: (layer_fn(x, p), None), x,
                        params["layers"])
    x = _ln(x, params["final_ln_scale"], params["final_ln_bias"], c.norm_eps)
    logits = x[:, 0] @ params["head_w"] + params["head_b"]
    return logits.astype(jnp.float32)


def loss_fn(params, batch, config: ViTConfig, mesh=None, rules=None):
    """Softmax CE classification loss. batch: {"images", "labels"}."""
    logits = forward(params, batch["images"], config)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)[:, 0]
    return jnp.mean(nll)
