"""Multi-head latent attention (MLA, DeepSeek-V2 2024): queries and
keys/values go through low-rank bottlenecks, and the decode cache holds
the bottleneck, not the heads. Three published users: LongCat-Flash (the
two scale factors, plain rotation, softmax_factor 1), A.X-K1 (the
DeepSeek-V3 family's: no scale factors, YaRN's frequencies on the rope
part, softmax_factor = mscale^2 = 1.81326) and Kimi Linear's full
layers (``q_rank`` None: the queries one projection with no norm;
``rotate`` False: no position enters, the "rope" part of q and the
shared key go into the score as projected).

On its normed input u at position t, with H heads::

    c_q = RMSNorm(W_qa u)                        in R^q_rank
    q   = q_scale * (W_qb c_q)                   in [H, nope + rope]
          (q_rank None: q = q_scale * (W_q u), no bottleneck, no norm)
    [c_kv ; k_r] = W_kva u                       in R^kv_rank + R^rope
    c   = kv_scale * RMSNorm(c_kv)               in R^kv_rank
    k_rope = RoPE_t(k_r)   (ONE head, shared);   q_rope = RoPE_t(q[:, nope:])
          (rotate False: k_rope = k_r, q_rope = q[:, nope:])
    k_nope_h = W_UK,h c,   v_h = W_UV,h c        in R^nope, R^v
    score_h(t, j) = (q_nope_h . k_nope_h(j) + q_rope_h . k_rope(j))
                    * softmax_factor / sqrt(nope + rope),  causal
    out = W_o concat_h(softmax_j(score_h) v_h(j))

(rotation on interleaved pairs (2j, 2j + 1), by the layer's `RopeSpec`
- `LatentSpec.rope`: plain frequencies or YaRN's, cos and sin times its
``scale`` - on the ONE shared key and on the queries' rope part alike,
in every form below; the whole softmax scale is multiplied into q once,
so neither the walk nor the kernel knows the factor). The cache row of a
position is ``[c ; k_rope]`` - ``kv_rank + rope`` numbers, NO head
axis - in one leaf, ``cached_latent`` [B, max_len, stored], beside its
``cache_index``. ``stored`` is the row padded with zeros to a multiple
of 128 (`LatentSpec.stored`: 576 -> 640 at LongCat's widths). The
chip's tiled layout holds 128 lanes a row whatever the shape says, and
a leaf whose rows are NOT lane-aligned the TPU compiler stores
position-minor instead ({2,3,1,0} for [slots, 1, 4096, 576]) - the
append and the decode kernel, which want whole rows, then each pay a
relayout copy of the whole leaf, twice a sublayer and step (sandbox
compile, PR 32: 16 copies of 302 MB in one tick). The pad is read with
the row (a ninth more bytes) and multiplies zeros in the query.

TWO FORMS of the one function, chosen by the code from the phase and
the shape, never by an option:

* **expanded** - k_nope and v are made from c and the heads attend as
  any softmax layer's do. The full forward pass (``decode=False``), the
  cache-shaping init and the one-pass prefill from an empty cache
  (`models.generate`): every position's c is in hand and is expanded
  once.
* **absorbed** - W_UK and W_UV are folded to the two sides of the
  attention: ``q~_h = W_UK,h^T q_nope_h`` in R^kv_rank, ``score = q~_h .
  c_j + q_rope_h . k_rope(j)``, ``o_h = W_UV,h (sum_j p_hj c_j)``. The
  heads then all read the SAME row, once, as key and as value, and
  nothing H-wide is ever made from the cache. The S = 1 step (on a TPU
  the ragged kernel `ops.flash_attention.flash_decode_attention` in its
  latent form, the row appended in place by `flash_cache_append`;
  elsewhere and under ``decode_prefix_impl="lax"`` the walk below, the
  oracle) and - by `chunk_form` - a prefill chunk or verify block of S
  rows against a cache that is not empty, while S is small: a cached
  position costs the absorbed form ``2 S H (2 kv_rank + rope)`` flops
  and the expanded one ``2 kv_rank H (nope + v)`` for its expansion
  plus ``2 S H (nope + rope + v)``; at LongCat's widths they cross at
  S = 171, so the engine's chunks (at most 128 rows) are absorbed and
  a longer block is expanded a walk block at a time.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

import flax.linen as nn

from horovod_tpu.parallel.tensor import (
    ColumnParallelDense, RopeSpec, RowParallelDense, _mesh_is_trivial,
    append_rows, apply_rope,
)


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """The widths of a latent-attention layer (`LatentAttention`), by
    their published names: ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``; and
    LongCat's two scale factors, on q and on the normed latent
    (`mla_scale_q_lora`, `mla_scale_kv_lora`: sqrt(hidden / rank)
    there; 1.0 = none). ``rope`` is the rotary rule of the rope part
    (None: plain frequencies at the layer's ``rope_theta``; a
    `RopeSpec` with ``yarn_factor`` is the DeepSeek family's YaRN),
    ``softmax_factor`` what multiplies 1 / sqrt(nope + rope) (that
    family's mscale(factor, mscale_all_dim)^2; 1.0 = none).
    ``q_rank`` None (`q_lora_rank: null`): the queries are ONE
    projection ``q`` from the hidden width, with no norm. ``rotate``
    False (`mla_use_nope: true`): there is NO rotary rule - ``rope`` is
    not read, the rope part of q and the shared key stay as projected,
    and the row, the absorbed query, the walk and the kernel keep their
    shapes."""
    q_rank: Optional[int]
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    q_scale: float = 1.0
    kv_scale: float = 1.0
    rope: Optional[RopeSpec] = None
    softmax_factor: float = 1.0
    rotate: bool = True

    @property
    def row(self) -> int:
        """Numbers a cached position holds."""
        return self.kv_rank + self.rope_dim

    @property
    def stored(self) -> int:
        """Width of a cached row as the leaf stores it: `row` padded
        with zeros to whole 128-lane tiles (the module's docstring)."""
        return -(-self.row // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return (self.softmax_factor
                * (self.nope_dim + self.rope_dim) ** -0.5)


def chunk_form(S: int, num_heads: int, spec: LatentSpec) -> str:
    """"absorbed" | "expanded": the cheaper way for S query rows to
    attend a cached position (the module's docstring has the count)."""
    absorbed = 2 * S * num_heads * (2 * spec.kv_rank + spec.rope_dim)
    expanded = (2 * spec.kv_rank * num_heads * (spec.nope_dim + spec.v_dim)
                + 2 * S * num_heads
                * (spec.nope_dim + spec.rope_dim + spec.v_dim))
    return "absorbed" if absorbed <= expanded else "expanded"


def latent_decode_plan(lanes: int, W: int, num_heads: int,
                       spec: LatentSpec, *, itemsize: int, S: int = 1,
                       impl: Optional[str] = None):
    """`ops.flash_attention.decode_attention_plan` for a latent layer's
    step over ``lanes`` caches of ``W`` rows: one head-less leaf of
    `stored`-wide rows whose first ``kv_rank`` columns are the values."""
    from horovod_tpu.ops.flash_attention import decode_attention_plan
    return decode_attention_plan(
        lanes, W, num_heads, 1, spec.stored, itemsize=itemsize, S=S,
        impl=impl, trivial_mesh=_mesh_is_trivial(), latent=spec.kv_rank)


def _softmax_attend(q, k, v, qpos, kpos):
    """q [..., Sq, H, Dk] (scaled) at positions qpos against k
    [..., Sk, H, Dk], v [..., Sk, H, Dv] at kpos, causal; float32
    softmax, the probabilities on the values' dtype."""
    s = jnp.einsum("...qhd,...khd->...hqk", q, k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(kpos[None, :] <= qpos[:, None], s,
                  jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...hqk,...khd->...qhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def latent_walk(q, rows, i, *, spec: LatentSpec, block: int,
                expand=None, count=None):
    """S query rows at positions i .. i + S - 1 against the cache's
    filled prefix, a block of rows at a time with a trip count that
    follows the fill (`ParallelSelfAttention._prefix_attention`'s walk
    over latent rows): online softmax in float32.

    ``rows`` [..., W, stored], the S new rows already in it.
    Absorbed (``expand`` None): ``q`` [..., S, H, stored] is
    ``[q~ ; q_rope ; 0]``, scaled; returns sum_j p_j c_j, [..., S, H,
    kv_rank] float32 - W_UV is the caller's. Expanded: ``expand`` =
    (W_UK [kv_rank, H, nope], W_UV [kv_rank, H, v]), ``q`` [..., S, H,
    nope + rope]; each block's keys and values are made from its rows;
    returns [..., S, H, v]. ``count`` (`LatentAttention.__call__`): the
    filled prefix ends at i + count."""
    S, H = q.shape[-3], q.shape[-2]
    r = spec.kv_rank
    Dv = spec.v_dim if expand else r
    lead = q.shape[:-3]
    qpos = i + jnp.arange(S, dtype=jnp.int32)
    nblk = (i + (S if count is None else count) + block - 1) // block
    neg = jnp.finfo(jnp.float32).min

    def body(j, carry):
        m, l, acc = carry
        start = j * block
        blk = lax.dynamic_slice_in_dim(rows, start, block, axis=-2)
        if expand:
            k_up, v_up = expand
            c, kr = blk[..., :r], blk[..., r:spec.row]
            kn = jnp.einsum("...kr,rhn->...khn", c, k_up)
            kb = jnp.concatenate(
                [kn, jnp.broadcast_to(kr[..., None, :],
                                      (*kn.shape[:-1], kr.shape[-1]))],
                axis=-1)
            vb = jnp.einsum("...kr,rhv->...khv", c, v_up)
            logits = jnp.einsum("...qhd,...khd->...hqk", q, kb,
                                preferred_element_type=jnp.float32)
        else:
            vb = blk[..., :r]
            logits = jnp.einsum("...qhc,...kc->...hqk", q, blk,
                                preferred_element_type=jnp.float32)
        kvpos = start + jnp.arange(block, dtype=jnp.int32)
        logits = jnp.where(kvpos[None, :] <= qpos[:, None], logits, neg)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        eq = "...hqk,...khd->...hqd" if expand else "...hqk,...kd->...hqd"
        acc_new = acc * alpha[..., None] + jnp.einsum(
            eq, p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((*lead, H, S), neg, jnp.float32)
    l0 = jnp.zeros((*lead, H, S), jnp.float32)
    a0 = jnp.zeros((*lead, H, S, Dv), jnp.float32)
    _, l, acc = lax.fori_loop(0, nblk, body, (m0, l0, a0))
    return jnp.swapaxes(acc / l[..., None], -3, -2)


class LatentAttention(nn.Module):
    """One latent-attention sublayer (the module's docstring has the
    equations and the two forms). ``decode=True`` keeps the cache;
    ``chunked_prefill`` / ``decode_prefix_block`` /
    ``decode_prefix_impl`` mean what they mean on
    `ParallelSelfAttention`."""

    num_heads: int
    spec: LatentSpec
    out_features: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Optional[Any] = None
    decode: bool = False
    chunked_prefill: bool = False
    decode_prefix_block: Optional[int] = 256
    decode_prefix_impl: Optional[str] = None

    @nn.compact
    def __call__(self, u: jax.Array,
                 count: Optional[jax.Array] = None) -> jax.Array:
        """``count`` (traced int32 in 1 .. S; a chunk appended to a
        cache): as `ParallelSelfAttention.__call__`'s - the pad
        positions past it write no latent row and advance no index."""
        sp, H = self.spec, self.num_heads
        dense = dict(use_bias=False, dtype=self.dtype)
        init = nn.initializers.lecun_normal()

        def norm(name):
            return nn.RMSNorm(dtype=self.dtype, epsilon=self.norm_eps,
                              name=name)

        if sp.q_rank is None:
            q = ColumnParallelDense(H * (sp.nope_dim + sp.rope_dim),
                                    name="q", **dense)(u)
        else:
            cq = norm("q_a_norm")(
                ColumnParallelDense(sp.q_rank, name="q_a", **dense)(u))
            q = ColumnParallelDense(H * (sp.nope_dim + sp.rope_dim),
                                    name="q_b", **dense)(cq)
        q = q.reshape(*q.shape[:-1], H, sp.nope_dim + sp.rope_dim)
        if sp.q_scale != 1.0:
            q = q * jnp.asarray(sp.q_scale, q.dtype)
        kv = ColumnParallelDense(sp.row, name="kv_a", **dense)(u)
        c = norm("kv_a_norm")(kv[..., :sp.kv_rank])
        if sp.kv_scale != 1.0:
            c = c * jnp.asarray(sp.kv_scale, c.dtype)
        # W_kvb as its two halves, a head a column block, so that
        # either form contracts the half it needs where it lies
        k_up = self.param("k_up", init, (sp.kv_rank, H, sp.nope_dim),
                          jnp.float32).astype(c.dtype)
        v_up = self.param("v_up", init, (sp.kv_rank, H, sp.v_dim),
                          jnp.float32).astype(c.dtype)
        if self.decode:
            o = self._decode_attention(q, c, kv[..., sp.kv_rank:],
                                       k_up, v_up, count)
        else:
            q, rows = self._rotate(q, c, kv[..., sp.kv_rank:], 0)
            o = self._expanded_block(q, rows, k_up, v_up)
        o = o.astype(c.dtype).reshape(*o.shape[:-2], H * sp.v_dim)
        return RowParallelDense(self.out_features, name="out",
                                **dense)(o)

    def _rotate(self, q, c, kr, offset):
        """(q with its rope part rotated, the cache rows [c ; k_rope ;
        0] as stored) at absolute positions offset + arange(S); with
        `LatentSpec.rotate` off nothing turns and the offset is not
        read."""
        if self.spec.rotate:
            n = self.spec.nope_dim
            pos = offset + jnp.arange(q.shape[-3])
            rope = self.spec.rope or RopeSpec(theta=self.rope_theta)
            rule = dict(rope.rotation(self.spec.rope_dim),
                        interleaved=True)
            q = jnp.concatenate(
                [q[..., :n], apply_rope(q[..., n:], pos, **rule)],
                axis=-1)
            kr = apply_rope(kr[..., None, :], pos, **rule)[..., 0, :]
        return q, jnp.concatenate(
            [c, kr.astype(c.dtype), self._pad(c)], axis=-1)

    def _pad(self, like):
        """The zeros that fill a row (or the absorbed query) of
        ``like``'s leading shape up to `LatentSpec.stored`."""
        sp = self.spec
        return jnp.zeros((*like.shape[:-1], sp.stored - sp.row),
                         like.dtype)

    def _expanded_block(self, q, rows, k_up, v_up):
        """The expanded form over the current block alone (causal):
        the queries a block of 512 at a time, so that a whole
        sequence's [H, S, S] scores are never alive at once."""
        sp = self.spec
        S = q.shape[-3]
        c, kr = rows[..., :sp.kv_rank], rows[..., sp.kv_rank:sp.row]
        kn = jnp.einsum("...kr,rhn->...khn", c, k_up)
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(kr[..., None, :],
                                  (*kn.shape[:-1], sp.rope_dim))], -1)
        v = jnp.einsum("...kr,rhv->...khv", c, v_up)
        q = q * jnp.asarray(sp.softmax_scale, q.dtype)
        pos = jnp.arange(S)
        blk = math.gcd(S, 512)
        if blk == S:
            return _softmax_attend(q, k, v, pos, pos)

        def rows_of(start):
            qb = lax.dynamic_slice_in_dim(q, start, blk, axis=-3)
            return _softmax_attend(qb, k, v, start + jnp.arange(blk),
                                   pos)

        o = lax.map(rows_of, jnp.arange(0, S, blk))   # [S/blk, ..., blk]
        o = jnp.moveaxis(o, 0, -4)
        return o.reshape(*o.shape[:-4], S, *o.shape[-2:])

    def _decode_attention(self, q, c, kr, k_up, v_up, count=None):
        sp, H = self.spec, self.num_heads
        is_init = self.has_variable("cache", "cached_latent")
        cached = self.variable(
            "cache", "cached_latent", jnp.zeros,
            (*c.shape[:-1], sp.stored), c.dtype)
        index = self.variable("cache", "cache_index",
                              lambda: jnp.zeros((), jnp.int32))
        if not is_init:
            q, rows = self._rotate(q, c, kr, 0)
            return self._expanded_block(q, rows, k_up, v_up)
        S, W, i = q.shape[-3], cached.value.shape[-2], index.value
        q, rows = self._rotate(q, c, kr, i)
        if S > 1 and not self.chunked_prefill:
            # one-pass prefill: the cache is EMPTY by contract
            # (`ParallelSelfAttention._decode_attention`)
            if not isinstance(i, jax.core.Tracer) and int(i) != 0:
                raise ValueError(
                    "one-pass prefill (chunked_prefill=False) requires "
                    f"an empty cache, but cache_index={int(i)}")
            self._write(cached, index, rows, i, S)
            return self._expanded_block(q, rows, k_up, v_up)
        form = chunk_form(S, H, sp)
        if form == "absorbed":
            q = jnp.concatenate(
                [jnp.einsum("...shn,rhn->...shr", q[..., :sp.nope_dim],
                            k_up), q[..., sp.nope_dim:], self._pad(q)],
                axis=-1)
        plan = self._kernel_plan(q, cached.value, S)
        if plan is not None:
            from horovod_tpu.ops.flash_attention import (
                flash_cache_append, flash_decode_attention)
            pool = cached.value[..., None, :]        # [B, W, 1, stored]
            if plan.write == "kernel":
                pool, _ = flash_cache_append(
                    pool, None, rows[..., None, :], None, i)
                cached.value, index.value = pool[..., 0, :], i + 1
            else:
                self._write(cached, index, rows, i, 1)
                pool = cached.value[..., None, :]
            o = flash_decode_attention(
                q, pool, None, i + 1, block_k=plan.block_k,
                scale=sp.softmax_scale, latent=sp.kv_rank)
        else:
            self._write(cached, index, rows, i,
                        S if count is None else count)
            blk = min(self.decode_prefix_block or W, W)
            o = latent_walk(
                q * jnp.asarray(sp.softmax_scale, q.dtype),
                cached.value, i, spec=sp, block=blk if W % blk == 0
                else W, expand=(None if form == "absorbed"
                                else (k_up, v_up)), count=count)
        if form == "absorbed":
            o = jnp.einsum("...shr,rhv->...shv", o.astype(v_up.dtype),
                           v_up, preferred_element_type=jnp.float32)
        return o

    @staticmethod
    def _write(cached, index, rows, i, S):
        """``S``: the rows appended - or, for a chunk whose tail is
        pad, the TRACED count of its real rows (`__call__`'s
        ``count``): only those are written."""
        cached.value = append_rows(
            cached.value, rows, i, None if isinstance(S, int) else S,
            axis=-2)
        index.value = i + S

    def _kernel_plan(self, q, pool, S):
        """The `DecodePlan` of this step if it is the ragged kernel's
        (`latent_decode_plan`), else None."""
        if q.ndim != 4:
            return None
        plan = latent_decode_plan(
            q.shape[0], pool.shape[-2], self.num_heads, self.spec,
            itemsize=pool.dtype.itemsize, S=S,
            impl=self.decode_prefix_impl)
        return plan if plan.path == "kernel" else None
