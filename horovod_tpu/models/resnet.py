"""ResNet-50/101/152 — the flagship benchmark family.

The reference's headline number is 90 % scaling efficiency for ResNet-101
data-parallel training on 128 GPUs (`README.md:27-32`, BASELINE.md); this
is the TPU-first implementation used by `chip_smoke.py` and
`__graft_entry__.py`.

TPU design notes:
* NHWC layout, 3x3/1x1 convs — XLA tiles these directly onto the MXU.
* bfloat16 activations/weights with float32 BatchNorm statistics and
  float32 final logits: the standard TPU mixed-precision recipe.
* Per-replica (local) BatchNorm, matching the reference's pure-DP
  semantics (no cross-replica stat sync in Horovod v0.10); a `sync_bn`
  flag adds cross-replica mean/var psum as a TPU-native extension
  (axis name "data") for small per-device batches.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp
from jax import lax

ModuleDef = Any


def space_to_depth(x, b):
    """[N, H, W, C] -> [N, H/b, W/b, b*b*C]; channel packing is
    (row-in-block, col-in-block, channel) — the convention the kernel
    re-packs in `SpaceToDepthStem` and `inception._S2DStemConv` depend
    on (shared helper, public on purpose)."""
    N, H, W, C = x.shape
    x = x.reshape(N, H // b, b, W // b, b, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        N, H // b, W // b, b * b * C)


class SpaceToDepthStem(nn.Module):
    """The ResNet 7x7/s2 stem computed as a space-to-depth conv — the
    standard MLPerf TPU trick for MXU underfill at the input layer.

    The plain stem convolves [N,H,W,3] with a [7,7,3,F] kernel: a
    3-channel contraction fills ~3/128 of an MXU pass, so the stem's
    ~25 % share of early FLOPs runs at a few percent efficiency
    (docs/mfu.md culprit #1). Here the image is 4x4 space-to-depth'd
    to [N,H/4,W/4,48] and convolved with a [3,3,48,4F] re-pack of the
    SAME [7,7,3,F] parameter (stride 1, VALID), then a 2x2
    depth-to-space restores [N,H/2,W/2,F] — numerically identical to
    the plain stem (oracle: tests/test_models.py) with a 16x larger
    contraction dim.

    The parameter tree is exactly nn.Conv's ({"kernel": [7,7,C,F]})
    under the same module name, so `s2d_stem` is a pure compute-path
    flag: checkpoints and inits are interchangeable with the plain
    stem.

    Derivation (1-D, per output column p = 2P + a, a in {0,1}): the
    SAME-padded stride-2 conv reads original pixels 2p-2+u, u in
    [0,7). With the image zero-padded by (2, 6) the window for s2d
    cell P starts at padded pixel 4P and spans 12 pixels = 3 cells;
    sub-position a selects kernel taps w[4U+du-2a], which is the
    [7,7] kernel embedded at offset (2a, 2b) in a [12,12] zero block.
    The extra trailing zero-pad columns (6 vs SAME's 3) multiply
    zeros in both formulations, so equality is exact.
    """
    features: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        N, H, W, C = x.shape
        if H % 4 or W % 4:
            raise ValueError(
                f"space-to-depth stem needs H, W divisible by 4, got "
                f"{(H, W)}; use s2d_stem=False for this input")
        F = self.features
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (7, 7, C, F))
        x = jnp.pad(x, ((0, 0), (2, 6), (2, 6), (0, 0)))
        x = space_to_depth(x, 4).astype(self.dtype)

        k = kernel.astype(self.dtype)
        taps = []
        for a in (0, 1):
            for b in (0, 1):
                kab = jnp.zeros((12, 12, C, F), k.dtype)
                kab = kab.at[2 * a:2 * a + 7, 2 * b:2 * b + 7].set(k)
                taps.append(
                    kab.reshape(3, 4, 3, 4, C, F)
                    .transpose(0, 2, 1, 3, 4, 5)
                    .reshape(3, 3, 16 * C, F))
        # Output packing o*4 + a*2 + b — undone by the depth-to-space
        # below.
        w = jnp.stack(taps, axis=-1).reshape(3, 3, 16 * C, 4 * F)

        y = lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        P, Q = y.shape[1], y.shape[2]
        y = y.reshape(N, P, Q, F, 2, 2)
        return y.transpose(0, 1, 4, 2, 5, 3).reshape(
            N, 2 * P, 2 * Q, F)


class SampledBatchNorm(nn.Module):
    """BatchNorm whose train-time statistics come from a 1/``sample``
    slice of the batch (ghost-batch-style sampled statistics).

    Why: the r4 device profile measured BatchNorm statistics at 37.8 %
    of the ResNet-101 step (docs/mfu.md) — every feature map is
    re-read for the fwd mean/var and again for the bwd channel sums,
    and a reduction cannot fuse into the producing conv's epilogue
    under XLA. Computing statistics over ``batch[: B/sample]`` cuts
    that reduction traffic by ``sample`` in BOTH directions (autodiff
    pulls only the sampled rows through the stat grads) while the
    normalization itself — elementwise, fused into neighboring ops —
    still covers the full batch.

    ``sample=1`` is exact BatchNorm (oracle-tested against
    `nn.BatchNorm`); ``sample>1`` estimates the same statistics from
    fewer rows — the ghost-batch-normalization family (Hoffer et al.
    2017), here used for bandwidth rather than regularization. Eval
    (``use_running_average=True``) semantics are unchanged. The
    variable collections mirror `nn.BatchNorm` (params scale/bias,
    batch_stats mean/var); ``axis_name`` syncs sampled stats
    cross-replica exactly like `nn.BatchNorm` does (pmean of mean and
    mean-of-squares).
    """

    use_running_average: bool
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Optional[jnp.dtype] = None
    axis_name: Optional[str] = None
    sample: int = 4
    scale_init: Callable = nn.initializers.ones

    @nn.compact
    def __call__(self, x):
        C = x.shape[-1]
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((C,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((C,), jnp.float32))
        scale = self.param("scale", self.scale_init, (C,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (C,),
                          jnp.float32)
        if self.use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            n = max(1, x.shape[0] // max(1, self.sample))
            xs = lax.slice_in_dim(x, 0, n, axis=0)
            xs = xs.astype(jnp.float32)
            axes = tuple(range(xs.ndim - 1))
            mean = xs.mean(axes)
            mean2 = (xs * xs).mean(axes)
            if self.axis_name is not None:
                mean = lax.pmean(mean, self.axis_name)
                mean2 = lax.pmean(mean2, self.axis_name)
            var = jnp.maximum(mean2 - mean * mean, 0.0)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1 - m) * mean
                ra_var.value = m * ra_var.value + (1 - m) * var
        inv = lax.rsqrt(var + self.epsilon) * scale
        y = (x.astype(jnp.float32) - mean) * inv + bias
        return y.astype(self.dtype or x.dtype)


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        # Zero-init the last BN scale per the bag-of-tricks recipe: the
        # block starts as identity, which also speeds large-batch DP
        # training (Goyal et al. 2017 — the same paper the reference's
        # LR-warmup callback implements, horovod/keras/callbacks.py:89).
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 self.strides, name="proj_conv")(residual)
            residual = self.norm(name="proj_bn")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    width: int = 64
    dtype: jnp.dtype = jnp.bfloat16
    sync_bn: bool = False
    axis_name: str = "data"
    # MXU-friendly stem (SpaceToDepthStem): same parameters, same
    # outputs, 16x larger stem contraction dim. Off by default: the
    # one chip reading of it was throughput-neutral (docs/mfu.md).
    s2d_stem: bool = False
    # >1: train-time BN statistics from batch[: B/bn_sample]
    # (SampledBatchNorm) — attacks the measured 37.8 %-of-step BN stat
    # traffic (docs/mfu.md). 1 = exact nn.BatchNorm. The choice is a
    # model-config constant (not train-flag-dependent) so train and
    # eval share one variable tree.
    bn_sample: int = 1

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        conv = partial(nn.Conv, use_bias=False, padding="SAME",
                       dtype=self.dtype)
        bn_axis = self.axis_name if (self.sync_bn and train) else None
        if self.bn_sample > 1:
            norm = partial(SampledBatchNorm,
                           use_running_average=not train,
                           momentum=0.9, epsilon=1e-5,
                           dtype=self.dtype, axis_name=bn_axis,
                           sample=self.bn_sample)
        else:
            norm = partial(nn.BatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                           axis_name=bn_axis)

        x = x.astype(self.dtype)
        if self.s2d_stem:
            x = SpaceToDepthStem(self.width, dtype=self.dtype,
                                 name="stem_conv")(x)
        else:
            x = conv(self.width, (7, 7), (2, 2), name="stem_conv")(x)
        x = norm(name="stem_bn")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = BottleneckBlock(self.width * 2 ** i, strides,
                                    conv=conv, norm=norm)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x


ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3])
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3])
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3])
