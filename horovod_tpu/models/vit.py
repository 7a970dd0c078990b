"""Vision Transformer (Dosovitskiy et al. 2021) — encoder model family.

No reference equivalent (Horovod v0.10 predates ViT; its benchmark
family is the tf_cnn_benchmarks CNNs) — this extends the model zoo with
the modern image backbone, built TPU-first from the same parallel
primitives as the flagship LM:

* **Patchify = space-to-depth + one Dense**: a [B,H,W,C] image becomes
  [B, (H/p)(W/p), p*p*C] with a reshape/transpose and projects through
  a single matmul — the entire "stem" is one MXU-shaped contraction
  (p=16, C=3 -> 768-wide), unlike a CNN stem's 3-channel conv
  (cf. `resnet.py::SpaceToDepthStem`, which has to re-pack a conv to
  get the same effect).
* **Encoder blocks are `TransformerBlock(causal=False)`** — the exact
  TP (Megatron column/row) attention+MLP blocks of the LM, so tensor
  parallelism over ``model`` and sequence parallelism over ``seq``
  (ring/ulysses/flash impls, bidirectional) compose unchanged.
* **bf16 activations, fp32 LayerNorm/head** — the standard TPU recipe.
* Global-average pooling head (no CLS token): keeps the token count at
  exactly (H/p)(W/p), which divides SP degrees and kernel block sizes.

Works with `make_cnn_train_step` (no BatchNorm state; the empty
batch_stats collection is handled); `examples/jax_vit.py` trains it.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

import flax.linen as nn

from horovod_tpu.models.transformer import TransformerBlock

Dtype = Any


class VisionTransformer(nn.Module):
    num_classes: int = 1000
    patch: int = 16
    num_layers: int = 12
    num_heads: int = 12
    head_dim: int = 64
    mlp_ratio: int = 4
    dtype: Optional[Dtype] = jnp.bfloat16
    attn_impl: str = "blockwise"
    # (no `window`: sliding windows are causal-only; per-step remat
    # lives in make_cnn_train_step(remat=True), which checkpoints the
    # whole forward)

    @nn.compact
    def __call__(self, x: jax.Array, *, train: bool = False):
        B, H, W, C = x.shape
        p = self.patch
        if H % p or W % p:
            raise ValueError(
                f"image size {(H, W)} must be divisible by patch {p}")
        d = self.num_heads * self.head_dim
        # Patchify: space-to-depth then one Dense (a single [p*p*C, d]
        # MXU contraction).
        x = x.reshape(B, H // p, p, W // p, p, C)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(
            B, (H // p) * (W // p), p * p * C)
        x = nn.Dense(d, dtype=self.dtype, name="patch_embed")(x)
        n_tokens = x.shape[1]
        pos = self.param("pos", nn.initializers.normal(0.02),
                         (n_tokens, d), jnp.float32)
        x = (x + pos).astype(self.dtype)

        block = partial(TransformerBlock,
                        num_heads=self.num_heads,
                        head_dim=self.head_dim,
                        mlp_ratio=self.mlp_ratio,
                        dtype=self.dtype,
                        attn_impl=self.attn_impl,
                        causal=False)
        for i in range(self.num_layers):
            x = block(name=f"block_{i}")(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)
        x = x.mean(axis=1)  # global average pool over tokens
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name="head")(x)


# ViT-S/16 and ViT-B/16 (Dosovitskiy et al. 2021, Table 1).
ViT_S16 = partial(VisionTransformer, num_layers=12, num_heads=6,
                  head_dim=64)
ViT_B16 = partial(VisionTransformer, num_layers=12, num_heads=12,
                  head_dim=64)
