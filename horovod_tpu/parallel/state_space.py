"""State-space layers with a scalar decay a head (Mamba-2, the SSD
layer of "Transformers are SSMs", arXiv:2405.21060) and their recurrent
state in the decode cache.

Per head the layer keeps a state h in R^{P x N} (float32; P the head
size, N the state size) instead of keys and values:

    h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) (x) B_t
    y_t = h_t C_t + D x_t

with A < 0 a scalar a head, dt_t > 0 a head and position, and B_t, C_t
in R^N shared by the heads of a group. Three forms of the same
arithmetic:

* `ssm_step` - one position (the S = 1 decode tick);
* `ssm_recurrent` - position by position (the oracle of the tests);
* `ssm_chunked` - the chunkwise (SSD) form for S > 1 (a prefill chunk,
  from any state): in blocks of at most `SsmSpec.chunk` positions, with
  L the running sum of dt A inside a block, the block's own part is
  ((C B^T) o exp(L_i - L_j), j <= i) (dt x), the carried state adds
  exp(L_i) h_0 C_i, and the state at the block's end is
  exp(L_end) h_0 + sum_j exp(L_end - L_j) (dt_j x_j) (x) B_j. Decays
  enter only as exp(differences that are <= 0), so no decay - however
  strong - overflows (as `linear_attention.kda_chunked` insists).

THE STATE'S LAYOUT is [..., G, N, Q]: G groups, the state size N, and
the group's Q = (H / G) P inner channels (head-major) in the minor
axis. The published layout a head is [P, N]; here the heads of a group
lie side by side in the lanes, because then a step is what the
delta-rule step (`ops.kda_step`) already is on this chip: vectors over
the channels (decay, dt x, the read-out) are ROWS, B and C are columns,
the read-out is a sum down the rows, and a tile is whole lanes for any
head size (64 here, half a lane).

`Mamba2Mixer` is the flax layer: in_proj to [z | x B C | dt], a causal
depthwise short convolution WITH bias over x B C (its last K-1
pre-convolution rows ride the cache as `conv_tail`), SiLU, the state,
the gated norm RMSNorm(y silu(z)) w over the whole inner width, and
out_proj. dt, the gates, the norm, the state and its update run in
float32. State and tail are OVERWRITTEN each step
(`Mamba2Mixer.OVERWRITTEN`, which `models.transformer.overwritten_leaf`
reads); the S = 1 step over a cached state has two executors chosen by
`state_step_plan` (`ops.ssm_step.ssm_step_plan` under the ambient
mesh): `ssm_step` as XLA compiles it, and `ops.ssm_step`'s in-place
kernel, which keeps the state of a lane whose ``advance`` flag is off
itself (`Mamba2Mixer.KEPT_BY_KERNEL`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

import flax.linen as nn

from horovod_tpu.parallel.tensor import (
    ColumnParallelDense, RowParallelDense,
)

Dtype = Any
_HI = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class SsmSpec:
    """The widths of a state-space mixer (`TransformerLM.ssm`)."""
    num_heads: int              # H
    head_dim: int               # P
    state_size: int             # N
    groups: int = 1             # G: B and C are shared by H / G heads
    conv_taps: int = 4          # K
    chunk: int = 256            # the chunked form's largest block

    def __post_init__(self):
        if self.num_heads % self.groups:
            raise ValueError(
                f"{self.groups} groups do not divide "
                f"{self.num_heads} heads")

    @property
    def inner(self) -> int:
        """The inner width I = H P."""
        return self.num_heads * self.head_dim

    @property
    def group_inner(self) -> int:
        """Q: a group's channels, the state's minor axis."""
        return self.inner // self.groups

    @property
    def conv_width(self) -> int:
        """The channels the short convolution runs over: x | B | C."""
        return self.inner + 2 * self.groups * self.state_size

    @property
    def proj_width(self) -> int:
        """in_proj's outputs: z | x B C | dt."""
        return self.inner + self.conv_width + self.num_heads

    def state_shape(self, lanes: int) -> tuple:
        return (lanes, self.groups, self.state_size, self.group_inner)


def _rows(x, dt, A, groups):
    """A position's vectors over the channels, a group a row:
    (decay, dt x) [..., G, Q] from x [..., H, P], dt [..., H], A [H]."""
    H, P = x.shape[-2:]
    lead = x.shape[:-2]
    decay = jnp.broadcast_to(jnp.exp(dt * A)[..., None], x.shape)
    dtx = dt[..., None] * x
    return (decay.reshape(*lead, groups, H * P // groups),
            dtx.reshape(*lead, groups, H * P // groups))


def step_rows(state, decay, dtx, B, C):
    """`ssm_step` on the rows of `_rows`: state [..., G, N, Q]; decay,
    dtx [..., G, Q]; B, C [..., G, N]. Returns (y [..., G, Q], state).
    What `ops.ssm_step`'s kernel computes, as XLA compiles it."""
    s = (state * decay[..., None, :]
         + B[..., :, None] * dtx[..., None, :])
    return jnp.sum(s * C[..., :, None], axis=-2), s


def ssm_step(state, x, dt, A, B, C):
    """One position. state [..., G, N, Q]; x [..., H, P]; dt [..., H];
    A [H]; B, C [..., G, N]. Returns (y [..., H, P] without the D x
    term, state)."""
    y, s = step_rows(state, *_rows(x, dt, A, B.shape[-2]), B, C)
    return y.reshape(x.shape), s


def state_step_plan(lanes: int, spec: SsmSpec, positions: int = 1):
    """`ops.ssm_step.ssm_step_plan` for a `Mamba2Mixer` of ``spec``
    stepping ``lanes`` lanes by ``positions`` positions, under the
    ambient mesh."""
    from horovod_tpu.ops.ssm_step import ssm_step_plan
    from horovod_tpu.parallel.tensor import _mesh_is_trivial
    return ssm_step_plan(lanes, spec.groups, spec.state_size,
                         spec.group_inner, positions=positions,
                         trivial_mesh=_mesh_is_trivial())


def ssm_recurrent(state, x, dt, A, B, C):
    """Position by position over [B, T, ...] inputs (time on axis 1).
    Returns (y [B, T, H, P], state)."""
    def tick(s, xs):
        y, s = ssm_step(s, xs[0], xs[1], A, xs[2], xs[3])
        return s, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C))
    state, y = lax.scan(tick, state, xs)
    return jnp.moveaxis(y, 0, 1), state


def ssm_chunked(state, x, dt, A, B, C, *, chunk: int = 256):
    """The chunkwise form of `ssm_recurrent` (same arguments, same
    results to rounding): float32 throughout, T of any length (the tail
    is padded with positions that neither decay nor write)."""
    Bt, T, H, P = x.shape
    G, N = B.shape[-2:]
    Hg = H // G
    Cn = min(chunk, T)
    pad = -T % Cn
    if pad:
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, B, C))
    nb = (T + pad) // Cn

    def blocks(a, *tail):               # [B, T, ...] -> [nb, B, Cn, *tail]
        return jnp.moveaxis(a.reshape(Bt, nb, Cn, *tail), 1, 0)

    dtx = blocks(dt[..., None] * x, G, Hg, P)
    la = blocks(dt * A, G, Hg)          # log decay a position, <= 0
    Bb, Cb = blocks(B, G, N), blocks(C, G, N)
    incl = jnp.tril(jnp.ones((Cn, Cn), bool))[:, :, None, None]

    def block(h, xs):                   # h [B, G, N, Hg, P]
        dtx, la, Bm, Cm = xs
        L = jnp.cumsum(la, axis=1)                      # [B, Cn, G, Hg]
        m = jnp.exp(jnp.where(
            incl, L[:, :, None] - L[:, None, :], -jnp.inf))
        cb = jnp.einsum("bign,bjgn->bijg", Cm, Bm, precision=_HI)
        y = jnp.einsum("bijgh,bjghp->bighp", cb[..., None] * m, dtx,
                       precision=_HI)
        y = y + jnp.exp(L)[..., None] * jnp.einsum(
            "bign,bgnhp->bighp", Cm, h, precision=_HI)
        end = L[:, -1]                                  # [B, G, Hg]
        h = (jnp.exp(end)[:, :, None, :, None] * h
             + jnp.einsum(
                 "bjgn,bjghp->bgnhp", Bm,
                 jnp.exp(end[:, None] - L)[..., None] * dtx,
                 precision=_HI))
        return h, y

    h, y = lax.scan(block, state.reshape(Bt, G, N, Hg, P),
                    (dtx, la, Bb, Cb))                  # y [nb,B,Cn,G,Hg,P]
    y = jnp.moveaxis(y, 0, 1).reshape(Bt, nb * Cn, H, P)
    return y[:, :T], h.reshape(state.shape)


def gated_norm(y, z, scale, eps):
    """RMSNorm(y silu(z)) scale over the last axis (ONE group over the
    whole inner width: the gate multiplies BEFORE the norm)."""
    g = y * jax.nn.silu(z)
    return g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps) * scale


class Mamba2Mixer(nn.Module):
    """The Mamba-2 token mixer: x [B, S, d] -> [B, S, out_features].

    ``decode=True`` keeps the state and the convolution's tail in the
    "cache" collection (`state` [B, G, N, Q] and `conv_tail`
    [B, K-1, I + 2 G N], both float32): S = 1 runs `ssm_step`, S > 1
    the chunkwise form from whatever state the cache holds. Zeros are
    the right initial state.

    ``advance`` (bool, a scalar or [B]; None: every lane advances)
    says which lanes a cached S = 1 step may move. Only the kernel's
    path (`state_step_plan`) looks at it: there the step keeps the
    `state` of a lane that does not advance itself. Everything else a
    step overwrites is still the caller's to put back.

    ``count`` (traced int32 in 1 .. S; an S > 1 chunk whose tail is
    pad): the positions past the first ``count`` neither decay the
    state nor write to it (dt = 0 there, as `ssm_chunked` pads its own
    tail), and the convolution's tail kept for the next chunk is the
    last real positions', not the pads'."""

    # as `KDAAttention`'s: what a step overwrites, and what the
    # in-place kernel keeps itself for a lane that does not advance
    OVERWRITTEN = ("state", "conv_tail")
    KEPT_BY_KERNEL = ("state",)

    spec: SsmSpec
    out_features: int
    norm_eps: float = 1e-5
    dtype: Optional[Dtype] = None
    decode: bool = False

    @nn.compact
    def __call__(self, x: jax.Array,
                 advance: Optional[jax.Array] = None,
                 count: Optional[jax.Array] = None) -> jax.Array:
        sp = self.spec
        H, P, N, G, K = (sp.num_heads, sp.head_dim, sp.state_size,
                         sp.groups, sp.conv_taps)
        I, W = sp.inner, sp.conv_width
        B, S, _ = x.shape
        f32 = jnp.float32

        proj = ColumnParallelDense(sp.proj_width, use_bias=False,
                                   dtype=self.dtype, name="in_proj")(x)
        z, xbc, dt = jnp.split(proj.astype(f32), [I, I + W], axis=-1)
        conv = self.param("conv", nn.initializers.normal(0.5), (K, W),
                          f32)
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (W,),
                               f32)
        a_log = self.param("A_log", nn.initializers.zeros, (H,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H,), f32)
        skip = self.param("D", nn.initializers.ones, (H,), f32)
        scale = self.param("norm", nn.initializers.ones, (I,), f32)

        cached = self.decode and self.has_variable("cache", "state")
        if self.decode:
            state = self.variable("cache", "state", jnp.zeros,
                                  sp.state_shape(B), f32)
            tail = self.variable("cache", "conv_tail", jnp.zeros,
                                 (B, K - 1, W), f32)
        past = tail.value if cached else jnp.zeros((B, K - 1, W), f32)
        u = jnp.concatenate([past, xbc], axis=1)        # [B, S+K-1, W]
        xbc = jax.nn.silu(
            sum(conv[j] * u[:, j:j + S] for j in range(K)) + conv_bias)
        xs, Bm, Cm = jnp.split(xbc, [I, I + G * N], axis=-1)
        xs = xs.reshape(B, S, H, P)
        Bm, Cm = Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N)
        dt = jax.nn.softplus(dt + dt_bias)              # [B, S, H]
        if count is not None:
            dt = jnp.where((jnp.arange(S) < count)[:, None], dt, 0.0)
        A = -jnp.exp(a_log)

        h0 = state.value if cached else jnp.zeros(sp.state_shape(B), f32)
        plan = state_step_plan(B, sp, S)
        if cached and plan.path == "kernel":
            from horovod_tpu.ops.ssm_step import ssm_state_step
            y, h1 = ssm_state_step(
                h0, *_rows(xs[:, 0], dt[:, 0], A, G), Bm[:, 0],
                Cm[:, 0], advance, plan=plan)
            y = y.reshape(B, 1, H, P)
        elif S == 1:
            y, h1 = ssm_step(h0, xs[:, 0], dt[:, 0], A, Bm[:, 0],
                             Cm[:, 0])
            y = y[:, None]
        else:
            y, h1 = ssm_chunked(h0, xs, dt, A, Bm, Cm, chunk=sp.chunk)
        if cached:
            state.value = h1
            tail.value = (u[:, S:] if count is None else
                          lax.dynamic_slice_in_dim(u, count, K - 1, 1))
        y = (y + skip[:, None] * xs).reshape(B, S, I)
        y = gated_norm(y, z, scale, self.norm_eps)
        return RowParallelDense(self.out_features, use_bias=False,
                                dtype=self.dtype, name="out_proj")(
            y.astype(self.dtype or x.dtype))
