"""Delta-rule linear attention with per-channel decay (KDA, the layer of
Kimi Linear, arXiv:2510.26692) and its recurrent state in the decode
cache.

Per head the layer keeps a state S in R^{Dk x Dv} (float32) instead of
keys and values:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with alpha_t = exp(g_t) in (0, 1]^{Dk} a per-channel decay and beta_t =
sigmoid(.) in (0, 1) or, with `KDAAttention.allow_neg_eigval`, twice
that in (0, 2) (negative eigenvalues allowed). Two forms of the same
arithmetic:

* `kda_recurrent` - one position at a time (the S = 1 decode tick, and
  the oracle of the tests);
* `kda_chunked` - the chunkwise form for S > 1 (a prefill chunk, from
  any state): inside sub-chunks of 64 positions the rank-one updates
  are written in the WY representation (one unit-triangular solve per
  sub-chunk and head), the state is carried between sub-chunks, and
  everything else is matrix products. Decays enter only as
  exp(differences that are <= 0): between blocks of 16 positions
  through the block's first row as reference point, inside a block
  directly, so no decay - however strong - overflows.

`KDAAttention` is the flax layer: fused q|k|v projection, a causal
depthwise short convolution (its last K-1 pre-convolution rows ride the
cache as `conv_tail`), SiLU, L2-normalised q and k, low-rank decay and
output gates, a per-head RMSNorm on the output. Gates, norms, the state
and its update run in float32. A state is OVERWRITTEN each step, not
appended to: a decode lane that must not advance has to keep its old
`state` and `conv_tail`. The layer says so itself, in
`KDAAttention.OVERWRITTEN`, which `models.transformer.overwritten_leaf`
reads (`_freeze_cache_indices`, and the pool's bytes by kind).

The S = 1 step over a cached state has two executors, chosen by
`state_step_plan` (`ops.kda_step.kda_step_plan` under the ambient
mesh): `kda_step` as XLA compiles it, and `ops.kda_step`'s in-place
kernel - one call over all lanes that also keeps the state of a lane
whose ``advance`` flag is off, so that nobody has to select it after
(`KDAAttention.KEPT_BY_KERNEL`).
"""

from __future__ import annotations

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
CONV_TAPS = 4       # the short convolution's kernel size


def kda_step(state, q, k, v, g, beta):
    """One position. state [..., H, Dk, Dv]; q, k, g [..., H, Dk];
    v [..., H, Dv]; beta [..., H]. Returns (o [..., H, Dv], state)."""
    s = state * jnp.exp(g)[..., None]
    u = v - jnp.sum(s * k[..., None], axis=-2)
    s = s + (beta[..., None] * k)[..., None] * u[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def state_step_plan(lanes: int, num_heads: int, head_dim: int,
                    positions: int = 1):
    """`ops.kda_step.kda_step_plan` for a `KDAAttention` of
    ``num_heads`` heads of ``head_dim`` stepping ``lanes`` lanes by
    ``positions`` positions, under the ambient mesh."""
    from horovod_tpu.ops.kda_step import kda_step_plan
    from horovod_tpu.parallel.tensor import _mesh_is_trivial
    return kda_step_plan(lanes, num_heads, head_dim, head_dim,
                         positions=positions,
                         trivial_mesh=_mesh_is_trivial())


def kda_recurrent(state, q, k, v, g, beta):
    """Token by token over [B, T, H, .] inputs (time on axis 1).
    Returns (o [B, T, H, Dv], state)."""
    def tick(s, xs):
        o, s = kda_step(s, *xs)
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state, o = lax.scan(tick, state, xs)
    return jnp.moveaxis(o, 0, 1), state


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HI)


def kda_chunked(state, q, k, v, g, beta, *, chunk: int = 64,
                block: int = 16):
    """The chunkwise form of `kda_recurrent` (same arguments, same
    results to rounding): float32 throughout, T of any length (the
    tail is padded with positions that neither decay nor write)."""
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    if T <= block:
        C = BC = T
    else:
        BC = block
        C = min(chunk, -(-T // block) * block)
    pad = -T % C
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    N, NB = (T + pad) // C, C // BC

    def chunks(a):                      # [B, T, H, ...] -> [B, N, H, C, ...]
        a = a.reshape(B, N, C, *a.shape[2:])
        return jnp.moveaxis(a, 2, 3)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)          # log decay from the chunk's start

    def blocks(a):                      # [.., C, D] -> [.., NB, BC, D]
        return a.reshape(*a.shape[:-2], NB, BC, a.shape[-1])

    kb, qb, Gb = blocks(k), blocks(q), blocks(G)
    # Between blocks i > j: exp(G_t - G_s) = exp(G_t - Gn_i) exp(Gn_i - G_s)
    # with Gn_i the first row of block i; both exponents are <= 0.
    Gn = Gb[..., 0, :]                                  # [.., NB, Dk]
    row = jnp.exp(Gb - Gn[..., None, :])
    below = jnp.tril(jnp.ones((NB, NB), bool), -1)      # j < i
    col = kb[..., None, :, :, :] * jnp.exp(jnp.where(
        below[:, :, None, None],
        Gn[..., :, None, None, :] - Gb[..., None, :, :, :], -jnp.inf))
    a_kk = _mm("...iad,...ijbd->...iajb", kb * row, col)
    a_qk = _mm("...iad,...ijbd->...iajb", qb * row, col)
    # Inside a block, directly: sum_c x_a,c k_b,c exp(G_a,c - G_b,c), b <= a.
    incl = jnp.tril(jnp.ones((BC, BC), bool))
    e = jnp.exp(jnp.where(
        incl[:, :, None],
        Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf))
    d_kk = jnp.sum(kb[..., :, None, :] * kb[..., None, :, :] * e, -1)
    d_qk = jnp.sum(qb[..., :, None, :] * kb[..., None, :, :] * e, -1)
    eye = jnp.eye(NB, dtype=d_kk.dtype)[:, None, :, None]
    a_kk = (a_kk + d_kk[..., :, :, None, :] * eye).reshape(
        *a_kk.shape[:-4], C, C)
    a_qk = (a_qk + d_qk[..., :, :, None, :] * eye).reshape(
        *a_qk.shape[:-4], C, C)

    # u_t = v_t - S0^T (k_t e^{G_t}) - sum_{s<t} A_kk[t,s] beta_s u_s:
    # (I + tril(A_kk, -1) Diag(beta)) U = V - K~ S0, solved once for
    # the two right-hand sides (the WY pair U', W).
    m = (jnp.eye(C, dtype=a_kk.dtype)
         + jnp.tril(a_kk, -1) * beta[..., None, :])
    k_dec = k * jnp.exp(G)
    sol = jax.scipy.linalg.solve_triangular(
        m, jnp.concatenate([v, k_dec], -1), lower=True,
        unit_diagonal=True)
    u0, w = sol[..., :Dv], sol[..., Dv:]
    q_dec = q * jnp.exp(G)
    g_end = G[..., -1:, :]                              # [.., 1, Dk]
    k_end = k * jnp.exp(g_end - G)
    a_qk = jnp.tril(a_qk)

    def step(s, xs):
        u0, w, q_dec, a_qk, k_end, g_end, beta = xs
        bu = beta[..., None] * (u0 - _mm("...cd,...de->...ce", w, s))
        o = (_mm("...cd,...de->...ce", q_dec, s)
             + _mm("...ct,...te->...ce", a_qk, bu))
        s = (jnp.swapaxes(jnp.exp(g_end), -1, -2) * s
             + _mm("...cd,...ce->...de", k_end, bu))
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0)
               for a in (u0, w, q_dec, a_qk, k_end, g_end, beta))
    state, o = lax.scan(step, state, xs)                # o [N,B,H,C,Dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)       # [B,N,C,H,Dv]
    return o.reshape(B, N * C, H, Dv)[:, :T], state


class KDAAttention(nn.Module):
    """The KDA token mixer: x [B, S, d] -> [B, S, out_features].

    ``decode=True`` keeps the state and the convolution's tail in the
    "cache" collection (`state` [B, H, Dk, Dv] float32, `conv_tail`
    [B, K-1, 3 H D] at the compute dtype): S = 1 runs the recurrence,
    S > 1 the chunkwise form from whatever state the cache holds.
    Zeros are the right initial state. The convolution has `CONV_TAPS`
    taps and both low-rank gates the rank ``head_dim``.

    ``advance`` (bool, a scalar or [B]; None: every lane advances)
    says which lanes a cached S = 1 step may move. Only the kernel's
    path (`state_step_plan`) looks at it: there the step keeps the
    `state` of a lane that does not advance itself. Everything else a
    step overwrites is still the caller's to put back.

    ``count`` (traced int32 in 1 .. S; an S > 1 chunk whose tail is
    pad): the positions past the first ``count`` neither decay the
    state nor write to it (g = 0 and beta = 0 there, as `kda_chunked`
    pads its own tail), and the convolution's tail kept for the next
    chunk is the last real positions', not the pads'.

    ``allow_neg_eigval`` (the published key of the same name): beta =
    2 sigmoid(.) in (0, 2), so that I - beta k k^T may have a negative
    eigenvalue (Solar-Open2); False, or the key absent (Kimi Linear):
    beta = sigmoid(.) in (0, 1)."""

    # The cache variables a step overwrites: whoever steps a lane that
    # must not advance has to put the old values back.
    OVERWRITTEN = ("state", "conv_tail")
    # ... but for these, where `state_step_plan` says "kernel" and the
    # step was told which lanes advance: reading the old value after
    # the in-place step would make XLA copy it first.
    KEPT_BY_KERNEL = ("state",)

    num_heads: int
    head_dim: int
    out_features: int
    norm_eps: float = 1e-5
    dtype: Optional[Dtype] = None
    decode: bool = False
    allow_neg_eigval: bool = True

    @nn.compact
    def __call__(self, x: jax.Array,
                 advance: Optional[jax.Array] = None,
                 count: Optional[jax.Array] = None) -> jax.Array:
        H, D, K = self.num_heads, self.head_dim, CONV_TAPS
        F = H * D
        B, S, _ = x.shape
        f32 = jnp.float32

        def dense(n, name):
            return ColumnParallelDense(n, use_bias=False,
                                       dtype=self.dtype, name=name)

        qkv = dense(3 * F, "qkv")(x)                    # pre-convolution
        conv = self.param("conv", nn.initializers.normal(0.5),
                          (K, 3 * F), f32)
        decay = dense(F, "f_b")(dense(D, "f_a")(x))
        a_log = self.param("A_log", nn.initializers.zeros, (H,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (F,), f32)
        beta = jax.nn.sigmoid(dense(H, "b_proj")(x).astype(f32))
        if self.allow_neg_eigval:
            beta = 2.0 * beta
        gate = jax.nn.sigmoid(
            dense(F, "g_b")(dense(D, "g_a")(x)).astype(f32))
        g = (-jnp.exp(a_log)[:, None]
             * jax.nn.softplus(decay.astype(f32) + dt_bias)
             .reshape(B, S, H, D))
        if count is not None:
            real = jnp.arange(S) < count
            g = jnp.where(real[:, None, None], g, 0.0)
            beta = jnp.where(real[:, None], beta, 0.0)

        cached = self.decode and self.has_variable("cache", "state")
        if self.decode:
            state = self.variable("cache", "state", jnp.zeros,
                                  (B, H, D, D), f32)
            tail = self.variable("cache", "conv_tail", jnp.zeros,
                                 (B, K - 1, 3 * F), qkv.dtype)
        past = (tail.value if cached
                else jnp.zeros((B, K - 1, 3 * F), qkv.dtype))
        u = jnp.concatenate([past, qkv], axis=1)        # [B, S+K-1, 3F]
        y = sum(conv[j].astype(f32) * u[:, j:j + S].astype(f32)
                for j in range(K))
        q, k, v = (t.reshape(B, S, H, D)
                   for t in jnp.split(jax.nn.silu(y), 3, axis=-1))

        def l2(t):
            return t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

        q, k = l2(q) * D ** -0.5, l2(k)
        s0 = state.value if cached else jnp.zeros((B, H, D, D), f32)
        plan = state_step_plan(B, H, D, S)
        if cached and plan.path == "kernel":
            from horovod_tpu.ops.kda_step import kda_state_step
            o, s1 = kda_state_step(s0, q[:, 0], k[:, 0], v[:, 0],
                                   g[:, 0], beta[:, 0], advance,
                                   plan=plan)
            o = o[:, None]
        elif S == 1:
            o, s1 = kda_step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                             beta[:, 0])
            o = o[:, None]
        else:
            o, s1 = kda_chunked(s0, q, k, v, g, beta)
        if cached:
            state.value = s1
            tail.value = (u[:, S:] if count is None else
                          lax.dynamic_slice_in_dim(u, count, K - 1, 1))
        scale = self.param("o_norm", nn.initializers.ones, (D,), f32)
        o = (o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                           + self.norm_eps) * scale)
        o = (o.reshape(B, S, F) * gate).astype(self.dtype or x.dtype)
        return RowParallelDense(self.out_features, use_bias=False,
                                dtype=self.dtype, name="o_proj")(o)
