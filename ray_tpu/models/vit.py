"""ViT-B/16-class vision transformer (BASELINE #4: map_batches batch inference).

flax.linen; attention through ops/attention.flash_attention so the TPU path
uses the Pallas kernel.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from ray_tpu.ops.attention import flash_attention


class ViTAttention(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, T, D = x.shape
        H = self.num_heads
        qkv = nn.Dense(3 * D, dtype=self.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        heads = lambda a: a.reshape(B, T, H, D // H).transpose(0, 2, 1, 3)  # flash_attention's [B, H, T, D // H]
        o = flash_attention(heads(q), heads(k), heads(v), causal=False).transpose(0, 2, 1, 3)
        return nn.Dense(D, dtype=self.dtype, name="proj")(o.reshape(B, T, D))


class ViTBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        y = nn.LayerNorm(dtype=jnp.float32)(x)
        x = x + ViTAttention(self.num_heads, self.dtype)(y)
        y = nn.LayerNorm(dtype=jnp.float32)(x)
        y = nn.Dense(self.mlp_dim, dtype=self.dtype)(y)
        y = nn.gelu(y)
        return x + nn.Dense(x.shape[-1], dtype=self.dtype)(y)


class ViT(nn.Module):
    num_classes: int = 1000
    patch_size: int = 16
    hidden_dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, images):
        # images: [B, H, W, 3]
        x = nn.Conv(
            self.hidden_dim,
            (self.patch_size, self.patch_size),
            strides=(self.patch_size, self.patch_size),
            dtype=self.dtype,
            name="patch_embed",
        )(images)
        B, h, w, D = x.shape
        x = x.reshape(B, h * w, D)
        cls = self.param("cls", nn.initializers.zeros, (1, 1, D), jnp.float32)
        x = jnp.concatenate([jnp.broadcast_to(cls.astype(x.dtype), (B, 1, D)), x], axis=1)
        pos = self.param(
            "pos_embed", nn.initializers.normal(0.02), (1, h * w + 1, D), jnp.float32
        )
        x = x + pos.astype(x.dtype)
        for i in range(self.num_layers):
            x = ViTBlock(self.num_heads, self.mlp_dim, self.dtype, name=f"block_{i}")(x)
        x = nn.LayerNorm(dtype=jnp.float32)(x)
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x[:, 0])


def ViT_B16(num_classes: int = 1000, **kw):
    return ViT(num_classes=num_classes, **kw)


def ViT_Tiny(num_classes: int = 10, **kw):
    """Small variant for tests."""
    return ViT(
        num_classes=num_classes,
        hidden_dim=64,
        num_layers=2,
        num_heads=4,
        mlp_dim=128,
        patch_size=8,
        **kw,
    )
