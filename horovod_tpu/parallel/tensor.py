"""Tensor (model) parallelism: weight-sharded layers over the ``model`` axis.

No reference equivalent — Horovod v0.10 replicates every variable
(SURVEY §2.3 "TP: NO"). This is the TPU-native extension: Megatron-style
column/row-parallel pairs expressed the GSPMD way. Parameters carry
`flax.linen.Partitioned` metadata (via `nn.with_partitioning`), activations
are pinned with sharding constraints, and XLA's SPMD partitioner inserts
the single all-reduce per pair (after the row-parallel matmul) — the same
comm pattern Megatron-LM issues by hand with NCCL, but here it rides the
ICI ring and fuses with the surrounding compute.

Layout convention (1 all-reduce per MLP / attention block):
  column parallel:  kernel (in, out/TP)   — output activ. sharded on last dim
  row parallel:     kernel (in/TP, out)   — psum over ``model`` restores full
Explicit `shard_map`-ready functional forms are provided for code that
wants the collectives visible (`column_parallel_matmul` /
`row_parallel_matmul`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import flax.linen as nn

from horovod_tpu.parallel.mesh import (
    AXIS_DATA, AXIS_MODEL, AXIS_SEQ, UNCONSTRAINED, axis_size,
    constrain, ring_perms,
)
from horovod_tpu.parallel.sequence import banded_causal_mask

Dtype = Any


# ---------------------------------------------------------------------------
# Functional forms (for use inside shard_map with `axis_name` bound).
# ---------------------------------------------------------------------------

def _native_gqa(fn) -> bool:
    """True when `fn` (possibly functools.partial-wrapped) declares it
    consumes grouped K/V natively (fewer kv heads than q heads) — the
    `native_gqa` marker set by `ops.flash_attention.flash_attention`."""
    while hasattr(fn, "func"):
        fn = fn.func
    return bool(getattr(fn, "native_gqa", False))


def column_parallel_matmul(x: jax.Array, w_shard: jax.Array) -> jax.Array:
    """`x @ W[:, shard]` — input replicated, output column-sharded.

    No communication; the pairing row-parallel matmul carries the psum.
    """
    return x @ w_shard


def row_parallel_matmul(x_shard: jax.Array, w_shard: jax.Array,
                        axis_name: str = AXIS_MODEL) -> jax.Array:
    """`psum_tp(x[:, shard] @ W[shard, :])` — the one all-reduce of a
    column→row parallel pair (Megatron's `g` operator)."""
    return lax.psum(x_shard @ w_shard, axis_name)


# ---------------------------------------------------------------------------
# Latency-hiding collective matmuls (ring-overlapped AG/RS forms).
#
# The sequence-parallel Megatron layout turns the TP pair's all-reduce
# into all-gather (before the column matmul) + reduce-scatter (after the
# row matmul). Issued as monolithic collectives those serialize against
# the MXU; the ring-overlapped forms below interleave one `ppermute`
# hop with one shard-sized matmul per step, so on TPU the async
# collective-permute rides the ICI links WHILE the previous shard's
# matmul occupies the MXU — compute hides all but the first hop of
# comm ("collective matmul", Wang et al. ASPLOS'23; the same overlap
# XLA's `--xla_tpu_enable_async_collective_fusion`-era einsum rewrites
# perform inside GSPMD, here available to explicit shard_map code).
# The all-gather form rotates two streams in opposite directions, using
# both directions of each ICI link — N/2 steps instead of N-1.
# Both are plain jax primitives, so they are differentiable and the
# oracle tests pin equality (fwd and grad) against the monolithic forms.
# ---------------------------------------------------------------------------

def allgather_matmul(x_shard: jax.Array, w: jax.Array,
                     axis_name: str = AXIS_MODEL) -> jax.Array:
    """`all_gather(x_shard, tiled) @ w`, comm overlapped with compute.

    ``x_shard`` [s, K] is this device's row block of a [N*s, K] input
    (e.g. sequence-parallel activations entering a column-parallel
    matmul); ``w`` [K, F] is resident (replicated or a column shard).
    Returns the full [N*s, F] product, bit-ordered by source rank,
    without ever materializing the gathered [N*s, K] input: each step
    matmuls the shard in hand while the next shards arrive over both
    ring directions.
    """
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    s = x_shard.shape[0]
    fwd, bwd = ring_perms(axis_name)

    def put(out, block, src):
        z = jnp.zeros((), idx.dtype)
        return lax.dynamic_update_slice(
            out, block, (src * s,) + (z,) * (block.ndim - 1))

    # Own shard first: its matmul overlaps the first hop of both rings.
    own = x_shard @ w
    out = jnp.zeros((n * s, *own.shape[1:]), own.dtype)
    out = put(out, own, idx)
    hi, lo = x_shard, x_shard
    for step in range(1, n // 2 + 1):
        # After `step` hops: `hi` holds rank (idx - step)'s shard
        # (travelling forward), `lo` holds rank (idx + step)'s.
        hi = lax.ppermute(hi, axis_name, fwd)
        last = (step == n // 2) and (n % 2 == 0)
        if not last:
            lo = lax.ppermute(lo, axis_name, bwd)
        out = put(out, hi @ w, (idx - step) % n)
        # The two streams deliver the same shard only when 2·step ≡ 0
        # (mod n), i.e. the even-N half-way step — exactly `last`.
        if not last:
            out = put(out, lo @ w, (idx + step) % n)
    return out


def matmul_reducescatter(x: jax.Array, w_shard: jax.Array,
                         axis_name: str = AXIS_MODEL) -> jax.Array:
    """`psum_scatter(x @ w_shard, tiled)` — the row-parallel epilogue of
    the sequence-parallel pair — with each partial block's matmul
    computed just-in-time as its accumulator rides the ring.

    ``x`` [R, Ks] holds this device's contraction shard of the input
    (R divisible by N); ``w_shard`` [Ks, F] the matching row block of
    W. Returns this rank's [R/N, F] block of the reduced product: the
    step-t matmul of one [R/N, Ks] x-block overlaps the ppermute of the
    accumulator computed at step t-1.
    """
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    if x.shape[0] % n:
        raise ValueError(
            f"leading dim {x.shape[0]} not divisible by axis size {n}")
    c = x.shape[0] // n
    fwd, _ = ring_perms(axis_name)

    def chunk_mm(j):
        z = jnp.zeros((), idx.dtype)
        blk = lax.dynamic_slice(
            x, (j * c,) + (z,) * (x.ndim - 1), (c, *x.shape[1:]))
        return blk @ w_shard

    # Chunk j enters the ring at rank (j+1): after n-1 forward hops it
    # lands on rank j having accumulated every rank's partial product.
    acc = chunk_mm((idx - 1) % n)
    for t in range(1, n):
        acc = lax.ppermute(acc, axis_name, fwd)
        acc = acc + chunk_mm((idx - t - 1) % n)
    return acc


# ---------------------------------------------------------------------------
# GSPMD flax modules.
# ---------------------------------------------------------------------------

def _dense_kernel(mod: nn.Module, in_features: int, features: int,
                  kernel_sharding: Tuple[Optional[str], Optional[str]],
                  ) -> jax.Array:
    """The kernel of a parallel Dense at the module dtype — plain, or
    weight-only int8 when ``mod.weight_quant == "int8"``.

    Quantized layout: ``kernel_q`` int8 [in, out] + ``kernel_scale``
    f32 [out] (per-output-channel), dequantized on-chip via the SAME
    `ops.quantization.dequantize_int8` the oracle tests pin — inside a
    decode scan the int8 HBM read replaces the bf16 one (half the
    weight traffic) and XLA fuses the dequant into the consuming
    matmul. Real values come from `quantize_lm_params`; quantized init
    is structural (zeros). The scale is sharded like the kernel's
    output dim so column-parallel shards carry their own scales.
    """
    if mod.weight_quant == "int8":
        from horovod_tpu.ops.quantization import dequantize_int8
        q = mod.param(
            "kernel_q",
            nn.with_partitioning(nn.initializers.zeros,
                                 kernel_sharding),
            (in_features, features), jnp.int8)
        scale = mod.param(
            "kernel_scale",
            nn.with_partitioning(nn.initializers.ones,
                                 (kernel_sharding[1],)),
            (features,), jnp.float32)
        return dequantize_int8(q, scale, mod.dtype, axis=0)
    if mod.weight_quant is not None:
        raise ValueError(
            f"unsupported weight_quant {mod.weight_quant!r}")
    return jnp.asarray(mod.param(
        "kernel",
        nn.with_partitioning(mod.kernel_init, kernel_sharding),
        (in_features, features), jnp.float32), mod.dtype)


def _lora_delta(mod: nn.Module, x: jax.Array, in_features: int,
                features: int, out_sharding) -> Optional[jax.Array]:
    """The low-rank update `(x @ A) @ B · (alpha/r)` when
    ``mod.lora_rank > 0`` (LoRA, Hu et al. 2021), else None.

    A [in, r] starts lecun-normal and is replicated; B [r, out] starts
    ZERO (the adapter is an exact no-op at init) and shards like the
    kernel's output dim, so column-parallel adapters stay shard-local
    and the row-parallel adapter's contraction psum is inserted by
    GSPMD alongside the main kernel's. The base kernel stays frozen by
    the optimizer mask (`models.lora.lora_label_fn`), not by the
    module — grads still flow through both paths, and the r-rank
    bottleneck keeps the adapter matmuls negligible."""
    r = mod.lora_rank
    if not r:
        return None
    alpha = mod.lora_alpha if mod.lora_alpha is not None else float(r)
    a = mod.param(
        "lora_a",
        nn.with_partitioning(nn.initializers.lecun_normal(),
                             (None, None)),
        (in_features, r), jnp.float32)
    b = mod.param(
        "lora_b",
        nn.with_partitioning(nn.initializers.zeros, (None, out_sharding)),
        (r, features), jnp.float32)
    xa = jnp.asarray(x, mod.dtype) @ jnp.asarray(a, mod.dtype)
    return (xa @ jnp.asarray(b, mod.dtype)) * (alpha / r)


class ColumnParallelDense(nn.Module):
    """Dense with the kernel's output dim sharded over ``model``."""

    features: int
    use_bias: bool = True
    dtype: Optional[Dtype] = None
    kernel_init: Callable = nn.initializers.lecun_normal()
    axis: str = AXIS_MODEL
    weight_quant: Optional[str] = None   # None | "int8"
    lora_rank: int = 0                   # LoRA adapter rank (0 = off)
    lora_alpha: Optional[float] = None   # scale = alpha/r (default r)

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = _dense_kernel(self, x.shape[-1], self.features,
                               (None, self.axis))
        y = jnp.asarray(x, self.dtype) @ kernel
        delta = _lora_delta(self, x, x.shape[-1], self.features,
                            self.axis)
        if delta is not None:
            y = y + delta
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_partitioning(nn.initializers.zeros, (self.axis,)),
                (self.features,), jnp.float32)
            y = y + jnp.asarray(bias, self.dtype)
        # Pin only the feature dim; leading (batch/seq) dims stay
        # UNCONSTRAINED so the partitioner keeps whatever data/seq/expert
        # sharding the surrounding activations carry (None here would
        # force them replicated — a hidden all-gather, and an involuntary
        # full rematerialization in the backward pass).
        return constrain(y, *([UNCONSTRAINED] * (y.ndim - 1) + [self.axis]))


class RowParallelDense(nn.Module):
    """Dense with the kernel's input dim sharded over ``model``; GSPMD
    emits the all-reduce that completes the partial products."""

    features: int
    use_bias: bool = True
    dtype: Optional[Dtype] = None
    kernel_init: Callable = nn.initializers.lecun_normal()
    axis: str = AXIS_MODEL
    weight_quant: Optional[str] = None   # None | "int8"
    lora_rank: int = 0
    lora_alpha: Optional[float] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kernel = _dense_kernel(self, x.shape[-1], self.features,
                               (self.axis, None))
        y = jnp.asarray(x, self.dtype) @ kernel
        delta = _lora_delta(self, x, x.shape[-1], self.features, None)
        if delta is not None:
            y = y + delta
        # Feature dim pinned unsharded ⇒ the partial products over the
        # ``model``-sharded contraction are psum-reduced here; leading
        # dims stay UNCONSTRAINED to preserve data/seq sharding.
        y = constrain(y, *([UNCONSTRAINED] * (y.ndim - 1) + [None]))
        if self.use_bias:
            # Bias replicated: added once, after the reduction.
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), jnp.float32)
            y = y + jnp.asarray(bias, self.dtype)
        return y


class ParallelMLP(nn.Module):
    """Transformer MLP block: column-parallel up, row-parallel down —
    one all-reduce total."""

    hidden: int
    out: int
    dtype: Optional[Dtype] = None
    activation: Callable = nn.gelu
    weight_quant: Optional[str] = None
    lora_rank: int = 0
    lora_alpha: Optional[float] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h = ColumnParallelDense(self.hidden, dtype=self.dtype,
                                weight_quant=self.weight_quant,
                                lora_rank=self.lora_rank,
                                lora_alpha=self.lora_alpha,
                                name="wi")(x)
        h = self.activation(h)
        return RowParallelDense(self.out, dtype=self.dtype,
                                weight_quant=self.weight_quant,
                                lora_rank=self.lora_rank,
                                lora_alpha=self.lora_alpha,
                                name="wo")(h)


class ParallelSwiGLU(nn.Module):
    """LLaMA-family MLP: `down(silu(gate(x)) * up(x))` — gate and up
    column-parallel, down row-parallel; exactly one all-reduce per
    block (the row matmul's psum), same as `ParallelMLP`. No biases
    (the family convention).

    Gate and up are deliberately SEPARATE projections, not a fused
    [d, 2·hidden] kernel: a gate-first fused layout puts gate columns
    on the first half of the TP shards and up columns on the second,
    so the elementwise `silu(g) * u` would force a per-block GSPMD
    reshard under tensor parallelism. Two same-LHS matmuls stay
    shard-local (and XLA's dot-merger may still combine them on a
    single device)."""

    hidden: int
    out: int
    dtype: Optional[Dtype] = None
    # "silu" (LLaMA SwiGLU) | "gelu_tanh" (Gemma GeGLU — the
    # gelu_pytorch_tanh approximation, matching torch exactly).
    activation: str = "silu"
    weight_quant: Optional[str] = None
    lora_rank: int = 0
    lora_alpha: Optional[float] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kw = dict(use_bias=False, dtype=self.dtype,
                  weight_quant=self.weight_quant,
                  lora_rank=self.lora_rank,
                  lora_alpha=self.lora_alpha)
        if self.activation == "silu":
            act = nn.silu
        elif self.activation == "gelu_tanh":
            act = functools.partial(nn.gelu, approximate=True)
        else:
            raise ValueError(
                f"activation must be silu|gelu_tanh, got "
                f"{self.activation!r}")
        g = ColumnParallelDense(self.hidden, name="gate", **kw)(x)
        u = ColumnParallelDense(self.hidden, name="up", **kw)(x)
        return RowParallelDense(self.out, name="down",
                                **kw)(act(g) * u)


class ParallelSelfAttention(nn.Module):
    """Multi-head self-attention with heads sharded over ``model``.

    QKV projections are column parallel (each TP shard owns
    num_heads/TP heads end-to-end through softmax), the output projection
    is row parallel — one all-reduce per attention block, Megatron layout.
    `attn_fn` plugs in the inner attention (full softmax by default; a
    Pallas flash kernel or ring attention from
    `horovod_tpu.parallel.sequence` in the flagship model).

    ``decode=True``: autoregressive inference — K/V land in a "cache"
    collection ([B, max_len, H, D], head dim still ``model``-sharded so
    TP decode needs no resharding), each call appends the new token at
    `cache_index` via `dynamic_update_slice` and attends the 1-token
    query against the filled prefix. Initialize the cache by calling
    `model.init` on a [B, max_len] dummy (the flax convention).

    ``num_kv_heads`` (GQA, Ainslie et al. 2023): K/V carry only
    H_kv < H heads, shared by groups of H/H_kv query heads. The QKV
    projection and — crucially — the decode KV cache shrink by
    H/H_kv. Kernels that declare ``native_gqa`` (the Pallas flash
    kernel) receive K/V at H_kv width and index-map heads internally
    — no repeat ever materializes; every other kernel (dot,
    blockwise, ring, ...) gets K/V broadcast to the full head count
    right at the attention (`_repeat_kv`) and runs unchanged.
    H_kv = H (default None) is exact MHA with identical parameters.
    """

    num_heads: int
    head_dim: int
    dtype: Optional[Dtype] = None
    attn_fn: Optional[Callable] = None
    decode: bool = False
    num_kv_heads: Optional[int] = None
    pos_emb: str = "none"        # "none" | "rope"
    rope_theta: float = 10000.0
    # The rotary rule where it is more than a theta (a rotated part of
    # the head, YaRN's frequencies and scale); None = RopeSpec(theta=
    # rope_theta), the whole head rotated.
    rope: Optional["RopeSpec"] = None
    window: Optional[int] = None  # sliding-window (decode mask)
    # Decode-mode S>1 calls: False (default) = one-pass prefill from
    # an EMPTY cache through the model's kernel (flash-able; what
    # `models.generate` does); True = chunked prefill — attend the
    # cached prefix via the general cache-wide mask (correct for any
    # cache_index, at [S, cache_len] mask cost).
    chunked_prefill: bool = False
    weight_quant: Optional[str] = None   # None | "int8" (projections)
    # "int8": decode KV cache stored int8 with per-(position, head)
    # f32 scales over the head_dim — 2x the context length per byte of
    # HBM (and half the cache read traffic per tick); K/V are
    # quantized at cache-write time and dequantized at the module
    # dtype on read. Decode-mode only; ignored when decode=False.
    kv_quant: Optional[str] = None
    # Linear-cache decode attention reads the filled prefix in slices
    # of this many slots (`lax.fori_loop` with a data-dependent trip
    # count) instead of masking against all max_len slots — per-tick
    # cache HBM traffic follows the GENERATED length, not the cache
    # allocation (the dominant serving cost at large max_len). 0/None
    # = the cache-wide-mask path (also the fallback when the block
    # doesn't divide the cache length).
    decode_prefix_block: Optional[int] = 256
    # None (default): the code chooses (`ops.flash_attention.
    # decode_attention_plan`) — the ragged flash-decode kernel for an
    # S=1 step on a TPU (un-quantized cache, no serving mesh, head_dim
    # a multiple of 128 or one that packs whole into 128 lanes: the
    # cache stores `kv_pack` KV heads to a row), the fori_loop walk
    # for everything else (CPU, int8 KV, S>1 chunks, a mesh). "lax"
    # forces the walk (the oracle); "pallas" forces the kernel
    # wherever it can run at all (interpret mode off the chip) — what
    # tests need, not users.
    decode_prefix_impl: Optional[str] = None
    # Projections carry no bias by default (LLaMA-style); GPT-2-family
    # checkpoints (compat.hf) need them.
    use_bias: bool = False
    # Qwen2-style split: bias on the qkv projection but not on the
    # output projection. None = follow use_bias (GPT-2: both).
    out_bias: Optional[bool] = None
    # Output gate: the attention's output is multiplied by
    # sigmoid(x W_g) (no bias) before the output projection. True (or
    # "elementwise"): W_g: d -> H*D, a gate a channel; "head":
    # W_g: d -> H, one scalar a head.
    out_gate: Union[bool, str] = False
    # Width of the output projection (the residual stream's); None =
    # H*D, the models whose hidden size is heads x head_dim.
    out_features: Optional[int] = None
    # The scale of the scores before the softmax; None = head_dim **
    # -0.5, which every inner attention (and every kernel) applies
    # itself. Another scale multiplies q by its ratio to that one
    # right after the projection, so no kernel needs an operand for
    # it; where the ratio is a power of two (Granite: 1/64 at head 64
    # is 1/8 of 64 ** -0.5) that is exact in any dtype.
    softmax_scale: Optional[float] = None
    lora_rank: int = 0
    lora_alpha: Optional[float] = None

    @nn.compact
    def __call__(self, x: jax.Array,
                 mask: Optional[jax.Array] = None,
                 count: Optional[jax.Array] = None) -> jax.Array:
        """``count`` (traced int32 in 1 .. S; decode mode, a chunk
        appended to a cache): only the first ``count`` positions are
        the prompt's, the rest are pad - they write no cache row, lap
        no ring slot and advance no index; their outputs are the
        caller's to discard (a real query never sees a pad key: pads
        come after every real position, and attention is causal)."""
        H = self.num_heads
        Hkv = self.num_kv_heads or H
        if H % Hkv:
            raise ValueError(
                f"num_heads={H} not divisible by num_kv_heads={Hkv}")
        from horovod_tpu.parallel.sequence import check_window
        check_window(self.window)
        features = H * self.head_dim
        kv_features = Hkv * self.head_dim
        qkv = ColumnParallelDense(features + 2 * kv_features,
                                  use_bias=self.use_bias,
                                  weight_quant=self.weight_quant,
                                  lora_rank=self.lora_rank,
                                  lora_alpha=self.lora_alpha,
                                  dtype=self.dtype, name="qkv")(x)
        q = qkv[..., :features]
        if self.softmax_scale is not None:
            q = q * jnp.asarray(
                self.softmax_scale * self.head_dim ** 0.5, q.dtype)
        k = qkv[..., features:features + kv_features]
        v = qkv[..., features + kv_features:]

        def heads(t, n):
            # [B, ..., S, n*D] -> [B, ..., S, n, D], keeping batch on
            # ``data`` and sequence on ``seq`` (a fully-specified
            # constraint with None there would force batch/seq
            # replication — an all-gather per block). Unbatched [S, n*D]
            # input has no data dim to pin.
            t = t.reshape(*t.shape[:-1], n, self.head_dim)
            if t.ndim == 3:
                return constrain(t, AXIS_SEQ, AXIS_MODEL, None)
            return constrain(t, AXIS_DATA, *([None] * (t.ndim - 4)),
                             AXIS_SEQ, AXIS_MODEL, None)

        q, k, v = heads(q, H), heads(k, Hkv), heads(v, Hkv)
        if self.decode:
            # Cache stores the UNREPEATED Hkv heads (the GQA memory
            # win); _decode_attention broadcasts after the cache read
            # and applies RoPE at the absolute cache position.
            o = self._decode_attention(q, k, v, count)
        else:
            q, k = self._maybe_rope(q, k)
            o = self._dispatch_attn(q, k, v, mask)
        if self.out_gate not in (False, True, "elementwise", "head"):
            raise ValueError(
                f"out_gate must be a bool, 'elementwise' or 'head', "
                f"got {self.out_gate!r}")
        if self.out_gate:
            per_head = self.out_gate == "head"
            gate = ColumnParallelDense(H if per_head else features,
                                       use_bias=False,
                                       weight_quant=self.weight_quant,
                                       dtype=self.dtype, name="gate")(x)
            gate = jax.nn.sigmoid(gate.astype(jnp.float32))
            if per_head:
                gate = gate[..., None]              # [..., S, H, 1]
            else:
                gate = gate.reshape(o.shape)
            o = (o * gate).astype(o.dtype)
        o = o.reshape(*o.shape[:-2], features)
        if o.ndim == 2:
            o = constrain(o, AXIS_SEQ, AXIS_MODEL)
        else:
            o = constrain(o, AXIS_DATA, *([None] * (o.ndim - 3)),
                          AXIS_SEQ, AXIS_MODEL)
        ob = self.use_bias if self.out_bias is None else self.out_bias
        return RowParallelDense(self.out_features or features,
                                use_bias=ob,
                                weight_quant=self.weight_quant,
                                lora_rank=self.lora_rank,
                                lora_alpha=self.lora_alpha,
                                dtype=self.dtype, name="out")(o)

    def _maybe_rope(self, q, k, offset=0):
        """Rotate q/k at absolute positions offset+arange(S) when
        ``pos_emb == "rope"`` (single site for the rotation rule)."""
        if self.pos_emb != "rope":
            return q, k
        positions = offset + jnp.arange(q.shape[-3])
        rule = (self.rope or RopeSpec(theta=self.rope_theta)
                ).rotation(self.head_dim)
        return (apply_rope(q, positions, **rule),
                apply_rope(k, positions, **rule))

    def _repeat_kv(self, t: jax.Array) -> jax.Array:
        """Broadcast Hkv KV heads to the full H query heads (no-op for
        MHA). Head axis is -2: [..., S, Hkv, D] -> [..., S, H, D]."""
        reps = self.num_heads // (self.num_kv_heads or self.num_heads)
        if reps == 1:
            return t
        return jnp.repeat(t, reps, axis=-2)

    def _dispatch_attn(self, q, k, v, mask):
        """THE attn_fn / native-GQA / dot dispatch (single site —
        train, init trace, and prefill all route through here)."""
        if self.attn_fn is not None:
            if _native_gqa(self.attn_fn):
                # e.g. the Pallas flash kernel: K/V consumed at their
                # Hkv width via index maps — never pay the H/Hkv x
                # repeat materialization in HBM.
                return self.attn_fn(q, k, v, mask)
            return self.attn_fn(q, self._repeat_kv(k),
                                self._repeat_kv(v), mask)
        return dot_product_attention(q, self._repeat_kv(k),
                                     self._repeat_kv(v), mask)

    def _causal_block_attn(self, q, k, v):
        """Causal(+window) attention over the current block alone via
        the model's kernel (the attn_fn carries the band rule; the dot
        fallback materializes it)."""
        if self.attn_fn is not None:
            return self._dispatch_attn(q, k, v, None)
        pos = jnp.arange(q.shape[-3])
        m = banded_causal_mask(pos, pos, self.window)[None, None]
        return self._dispatch_attn(q, k, v, m)

    @property
    def _kv_pack(self) -> int:
        """KV heads the cache leaves store to a row (`ops.
        flash_attention.kv_pack`: 128 // head_dim of a head narrower
        than the chip's lanes, so that a stored row is lane-whole and
        the ragged kernel and the in-place append step it where it
        lies). Every other reader and writer of the leaves goes
        through `_stored` / `_heads` - reshapes. The int8 cache keeps
        a head a row: its scales are a head's, and it stays on the
        walk."""
        from horovod_tpu.ops.flash_attention import kv_pack
        if self.kv_quant is not None:
            return 1
        return kv_pack(self.num_kv_heads or self.num_heads,
                       self.head_dim)

    def _stored(self, t):
        """K or V rows [..., Hkv, D] as the cache leaves store them."""
        from horovod_tpu.ops.flash_attention import pack_kv_rows
        return pack_kv_rows(t, self._kv_pack)

    def _heads(self, t):
        """Stored rows (a leaf, a block of one, a paged pool) as
        [..., Hkv, D]."""
        from horovod_tpu.ops.flash_attention import unpack_kv_rows
        return unpack_kv_rows(t, self._kv_pack)

    def _kv_cache_vars(self, k, v, L0):
        """Cache storage for K/V (+ per-(position, head) scale vars
        when ``kv_quant``): [..., L0, Hkv // pack, D * pack]
        (`_kv_pack`). Shape args are only read at creation time
        (model.init)."""
        if self.kv_quant not in (None, "int8"):
            raise ValueError(
                f"unsupported kv_quant {self.kv_quant!r}")
        cache_shape = (*k.shape[:-3], L0, *self._stored(k).shape[-2:])
        store = jnp.int8 if self.kv_quant == "int8" else k.dtype
        cached_k = self.variable("cache", "cached_key",
                                 jnp.zeros, cache_shape, store)
        cached_v = self.variable("cache", "cached_value",
                                 jnp.zeros, cache_shape, store)
        if self.kv_quant == "int8":
            s_shape = (*k.shape[:-3], L0, k.shape[-2])
            scale_k = self.variable("cache", "cached_key_scale",
                                    jnp.ones, s_shape, jnp.float32)
            scale_v = self.variable("cache", "cached_value_scale",
                                    jnp.ones, s_shape, jnp.float32)
        else:
            scale_k = scale_v = None
        return cached_k, cached_v, scale_k, scale_v

    def _cache_read(self, cached, scale):
        """The cache at the compute dtype (dequantized under
        ``kv_quant`` via the single tested codec)."""
        if scale is None:
            return self._heads(cached.value)
        from horovod_tpu.ops.quantization import dequantize_int8
        return dequantize_int8(cached.value, scale.value,
                               self.dtype or jnp.float32, axis=-1)

    def _cache_write(self, cached_k, cached_v, scale_k, scale_v,
                     index, k, v, i, S, W):
        """Append S new K/V at position i (linear cache) or into their
        rolling slots (window cache); advances the index by S. Under
        ``kv_quant`` the block is quantized here (symmetric int8 over
        head_dim, one scale per (position, head)) and the scales land
        in the same slots. ``S`` is the rows of k and v - or, for a
        chunk whose tail is pad, the TRACED count of its real rows
        (`__call__`'s ``count``): only those are written, and every
        other row of the cache keeps what it held."""
        count, S = (None, S) if isinstance(S, int) else (S, k.shape[-3])
        if self.kv_quant == "int8":
            k, sk = _kv_quantize(k)
            v, sv = _kv_quantize(v)
        else:
            k, v = self._stored(k), self._stored(v)
        if self.window is None:
            cached_k.value = append_rows(cached_k.value, k, i, count,
                                         axis=1)
            cached_v.value = append_rows(cached_v.value, v, i, count,
                                         axis=1)
            if scale_k is not None:
                scale_k.value = append_rows(scale_k.value, sk, i,
                                            count, axis=1)
                scale_v.value = append_rows(scale_v.value, sv, i,
                                            count, axis=1)
        elif count is None:
            # Last min(S, W) keys land in their slots (earlier ones
            # would be overwritten within this block anyway).
            t = min(S, W)
            qpos = i + jnp.arange(S, dtype=i.dtype)
            slots = (qpos[S - t:]) % W
            cached_k.value = cached_k.value.at[:, slots].set(
                k[:, S - t:])
            cached_v.value = cached_v.value.at[:, slots].set(
                v[:, S - t:])
            if scale_k is not None:
                scale_k.value = scale_k.value.at[:, slots].set(
                    sk[:, S - t:])
                scale_v.value = scale_v.value.at[:, slots].set(
                    sv[:, S - t:])
        else:
            # The last min(count, W) REAL keys land in their slots;
            # every other row of the block (earlier real keys that
            # these overwrite anyway, and the pads - which would lap a
            # live slot of the ring) is sent past the ring's end and
            # dropped.
            r = jnp.arange(S, dtype=i.dtype)
            slots = jnp.where((r < count) & (r >= count - W),
                              (i + r) % W, W)

            def put(ring, rows):
                return ring.at[:, slots].set(rows, mode="drop")

            cached_k.value = put(cached_k.value, k)
            cached_v.value = put(cached_v.value, v)
            if scale_k is not None:
                scale_k.value = put(scale_k.value, sk)
                scale_v.value = put(scale_v.value, sv)
        index.value = i + (S if count is None else count)

    def _cache_read_block(self, cached, scale, start, size):
        """One `size`-slot slice of the cache at the compute dtype
        (dequantized under ``kv_quant``) — the prefix-attention read
        granularity: only slices covering the filled prefix are ever
        taken, so per-tick cache HBM traffic follows the generated
        length instead of the allocation."""
        blk = lax.dynamic_slice_in_dim(cached.value, start, size,
                                       axis=-3)
        if scale is None:
            return self._heads(blk)
        from horovod_tpu.ops.quantization import dequantize_int8
        sb = lax.dynamic_slice_in_dim(scale.value, start, size,
                                      axis=-2)
        return dequantize_int8(blk, sb, self.dtype or jnp.float32,
                               axis=-1)

    def _prefix_attention(self, q, cached_k, cached_v, scale_k,
                          scale_v, i, S, count=None):
        """Decode attention that touches ONLY the filled cache prefix.

        The cache-wide-mask path reads (and masks against) all
        ``max_len`` K/V slots every tick, so per-tick HBM traffic
        scales with the cache ALLOCATION — at serving shapes that is
        the dominant cost (10 ms/tick against a ~1.5 ms full-cache
        roofline in the one 2026-07-31 v5e run, with most of the
        cache not even filled). Here the filled prefix [0, i+S) is consumed in
        ``decode_prefix_block``-slot slices inside a `lax.fori_loop`
        with a data-dependent trip count; softmax is the standard
        online (flash) accumulation in f32 (Milakov & Gimelshein
        2018), so the result matches the cache-wide path to numerical
        tolerance while reading ceil((i+S)/block)·block slots.

        q: [..., S, H, D]; returns [..., S, H, D]. Composes with GQA
        (per-block `_repeat_kv`), int8 KV (per-block dequant), and TP
        (all ops are shard-local over the head axis). With ``count``
        (`__call__`) the prefix ends at i + count: the pad queries
        past it read what the real ones do, and are discarded.
        """
        W = cached_k.value.shape[-3]
        blk = min(self.decode_prefix_block, W)
        H = self.num_heads
        D = self.head_dim
        lead = q.shape[:-3]
        dtype = q.dtype
        q = q * jnp.asarray(D ** -0.5, dtype)
        qpos = i + jnp.arange(S, dtype=jnp.int32)          # [S]
        filled = i + (S if count is None else count)
        nblk = (filled + blk - 1) // blk                   # traced
        neg = jnp.finfo(jnp.float32).min
        m0 = jnp.full((*lead, H, S), neg, jnp.float32)
        l0 = jnp.zeros((*lead, H, S), jnp.float32)
        a0 = jnp.zeros((*lead, H, S, D), jnp.float32)

        def body(j, carry):
            m, l, acc = carry
            start = j * blk
            kb = self._repeat_kv(self._cache_read_block(
                cached_k, scale_k, start, blk))
            vb = self._repeat_kv(self._cache_read_block(
                cached_v, scale_v, start, blk))
            logits = jnp.einsum("...qhd,...khd->...hqk", q, kb,
                                preferred_element_type=jnp.float32)
            kvpos = start + jnp.arange(blk, dtype=jnp.int32)
            keep = kvpos[None, :] <= qpos[:, None]         # [S, blk]
            logits = jnp.where(keep, logits, neg)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            # p rides the MXU at the cache dtype (flash-kernel
            # practice); accumulation stays f32.
            acc_new = (acc * alpha[..., None]
                       + jnp.einsum("...hqk,...khd->...hqd",
                                    p.astype(vb.dtype), vb,
                                    preferred_element_type=jnp.float32))
            return m_new, l_new, acc_new

        m, l, acc = lax.fori_loop(0, nblk, body, (m0, l0, a0))
        out = acc / l[..., None]                     # [..., H, S, D]
        return jnp.swapaxes(out, -3, -2).astype(dtype)

    def _kernel_plan(self, q, cached_k, scale_k, S, ring=False):
        """The `DecodePlan` of this step if it is the ragged kernel's
        (`ops.flash_attention.decode_attention_plan`: S = 1, an
        un-quantized cache, at a shape Mosaic takes, on a TPU), else
        None. Trivial-mesh only: a bare pallas_call is opaque to the
        GSPMD partitioner, so sharded (TP) decode keeps the lax path,
        whose ops partition over the head axis naturally."""
        from horovod_tpu.ops.flash_attention import decode_attention_plan
        if q.ndim != 4:
            return None
        plan = decode_attention_plan(
            q.shape[0], cached_k.value.shape[-3], self.num_heads,
            self.num_kv_heads or self.num_heads, self.head_dim,
            itemsize=cached_k.value.dtype.itemsize,
            S=S, impl=self.decode_prefix_impl,
            quantized=scale_k is not None,
            trivial_mesh=_mesh_is_trivial(), ring=ring)
        return plan if plan.path == "kernel" else None

    def _kernel_step(self, plan, q, k, v, cached_k, cached_v, index,
                     i, slot, length):
        """The ragged kernel's S = 1 step: the new row into ``slot``,
        then the first ``length`` slots attended. Where the plan's
        write is the kernel's too, the row reaches the cache by
        `flash_cache_append` - every lane of the tick's vmap in one
        in-place call; `_cache_write`'s update at a batched index is a
        scatter, which XLA for the TPU runs a lane at a time."""
        from horovod_tpu.ops.flash_attention import (
            flash_cache_append, flash_decode_attention)
        if plan.write == "kernel":
            cached_k.value, cached_v.value = flash_cache_append(
                cached_k.value, cached_v.value, k, v, slot)
            index.value = i + 1
        else:
            self._cache_write(cached_k, cached_v, None, None, index,
                              k, v, i, 1, cached_k.value.shape[-3])
        return flash_decode_attention(
            q, cached_k.value, cached_v.value, length,
            block_k=plan.block_k)

    def _paged_attention(self, q, k, v, cached_k, cached_v,
                         scale_k, scale_v, index, i, S, W):
        """Decode/prefill attention against a PAGED cache: the block
        pools + this lane's table/fill arrive via the read-only
        "paged" collection (`models.transformer._paged_collection`),
        the call's new K/V rows land in the tiny [1, S] staging cache
        (position 0 — the tick scatters them into their blocks
        afterwards), and the attention walks only the FILLED blocks
        (`ops.paged_attention`). RoPE rotates at the TRUE fill (the
        staging index is always 0). The walk at
        ``decode_prefix_block`` granularity is bitwise the
        gathered-view path."""
        k_pool = self.get_variable("paged", "key_pool")
        v_pool = self.get_variable("paged", "value_pool")
        ks_pool = (self.get_variable("paged", "key_scale_pool")
                   if self.has_variable("paged", "key_scale_pool")
                   else None)
        vs_pool = (self.get_variable("paged", "value_scale_pool")
                   if self.has_variable("paged", "value_scale_pool")
                   else None)
        table = self.get_variable("paged", "table")
        fill = self.get_variable("paged", "fill")
        # the pools take the leaves' stored rows; the paged walk and
        # kernel read them a head a row
        k_pool, v_pool = self._heads(k_pool), self._heads(v_pool)
        q, k = self._maybe_rope(q, k, offset=fill)
        # Staging write at position 0 (i is the staging cache_index):
        # the rows pass through the same codec the pool stores, and
        # the read-back below is therefore byte-identical to what a
        # gathered view would hold at positions [fill, fill+S).
        self._cache_write(cached_k, cached_v, scale_k, scale_v,
                          index, k, v, i, S, W)
        k_ins = self._cache_read(cached_k, scale_k)
        v_ins = self._cache_read(cached_v, scale_v)
        bs = int(k_pool.shape[2])
        span = int(table.shape[-1]) * bs
        blk = self.decode_prefix_block
        if not blk:
            raise ValueError(
                "paged-kernel decode requires decode_prefix_block "
                "(the walk granularity); got 0/None")
        wb = min(int(blk), span)
        if wb % bs or span % wb:
            raise ValueError(
                f"paged-kernel decode needs decode_prefix_block "
                f"({blk}) to be a multiple of the KV block size "
                f"({bs}) and to divide max_len ({span})")
        from horovod_tpu.ops.paged_attention import (
            paged_prefix_attention)
        reps = self.num_heads // (self.num_kv_heads or self.num_heads)
        return paged_prefix_attention(
            q, k_ins, v_ins, k_pool, v_pool, table, fill,
            walk_block=wb, groups=reps,
            k_scale_pool=ks_pool, v_scale_pool=vs_pool,
            compute_dtype=self.dtype or jnp.float32)

    def _decode_attention(self, q, k, v, count=None):
        """One decode tick: append k/v at `cache_index`, attend q
        against the filled prefix (``count``: see `__call__`). At
        cache-init time (`model.init` on a [B, max_len] dummy) the
        cache is shaped from the full-length k/v and a plain causal
        forward runs instead.

        With a ``window``, the cache is a ROLLING buffer of only
        `window` entries (slot = position mod window): cache memory
        and per-tick attention cost are O(window), not O(max_len), and
        with RoPE the absolute position counter keeps growing, so
        generation length is unbounded by the cache."""
        is_init = self.has_variable("cache", "cached_key")
        # Cache length: full at plain decode, exactly `window` slots
        # when sliding-window — NOT min(init_len, window): a cache
        # shorter than the window would silently evict in-band keys
        # once the position counter passes the init length.
        L0 = k.shape[-3] if self.window is None else self.window
        cached_k, cached_v, scale_k, scale_v = self._kv_cache_vars(
            k, v, L0)
        index = self.variable("cache", "cache_index",
                              lambda: jnp.zeros((), jnp.int32))
        if not is_init:
            q, k = self._maybe_rope(q, k)
            return self._causal_block_attn(q, k, v)

        S = q.shape[-3]
        W = cached_k.value.shape[-3]
        i = index.value
        if self.has_variable("paged", "key_pool"):
            # Paged-kernel serving mode (ops/paged_attention.py): the
            # "cache" collection holds only a [1, S] STAGING buffer
            # for this call's new rows (cache_index = 0), and the
            # real KV lives in the shared block pools the "paged"
            # collection carries — attention walks the pools through
            # the lane's block table, touching only filled blocks,
            # instead of reading a gathered [max_len] view.
            return self._paged_attention(
                q, k, v, cached_k, cached_v, scale_k, scale_v,
                index, i, S, W)
        # Rotate at the ABSOLUTE position; keys enter the cache
        # already rotated, so the prefix needs no re-rotation.
        q, k = self._maybe_rope(q, k, offset=i)

        if S > 1 and not self.chunked_prefill:
            # ONE-PASS PREFILL — the S>1 decode-mode call
            # `models.generate` makes; contract: the cache is EMPTY
            # (i = 0), so attending the cached prefix equals causal
            # (+window) attention over the current block alone. Runs
            # through the model's kernel (flash: VMEM-tiled, banded
            # under a window, GQA-native) — prefill cost follows the
            # PROMPT, never a [S, cache_len] mask materialized against
            # max_len/window slots. For S>1 appends to a NON-empty
            # cache, set ``chunked_prefill=True`` to keep the general
            # cache-wide-mask path below (correct for any i).
            # Best-effort contract enforcement: with a concrete index
            # (eager apply) a non-empty cache is a hard error instead
            # of silently attending only the current block; under jit
            # `i` is a tracer and the contract stays documented-only.
            if not isinstance(i, jax.core.Tracer) and int(i) != 0:
                raise ValueError(
                    "one-pass prefill (chunked_prefill=False) requires "
                    f"an empty cache, but cache_index={int(i)}; use "
                    "chunked_prefill=True for S>1 appends to a "
                    "non-empty cache")
            self._cache_write(cached_k, cached_v, scale_k, scale_v,
                              index, k, v, i, S, W)
            return self._causal_block_attn(q, k, v)

        if self.window is None:
            # Write first, then attend over the (possibly dequantized)
            # updated cache — the current token reads back through the
            # same codec later ticks will see.
            blk = self.decode_prefix_block
            prefix = blk and W % min(blk, W) == 0
            plan = prefix and self._kernel_plan(q, cached_k, scale_k, S)
            if plan:
                return self._kernel_step(plan, q, k, v, cached_k,
                                         cached_v, index, i, i, i + 1)
            self._cache_write(cached_k, cached_v, scale_k, scale_v,
                              index, k, v, i,
                              S if count is None else count, W)
            if prefix:
                return self._prefix_attention(q, cached_k, cached_v,
                                              scale_k, scale_v, i, S,
                                              count)
            key = self._cache_read(cached_k, scale_k)
            val = self._cache_read(cached_v, scale_v)
            # Valid positions: the prefix plus the causal part of the
            # new block — position p attends to cached positions
            # <= i + its own offset.
            mask = banded_causal_mask(i + jnp.arange(S), jnp.arange(W),
                                      None)[None, None]
            return dot_product_attention(q, self._repeat_kv(key),
                                         self._repeat_kv(val), mask)

        # Rolling window, one position (S = 1): position i lands in
        # slot i mod W - the slot of position i - W, which is outside
        # i's band - and the ring then holds exactly the band: its
        # first min(i + 1, W) slots, every one once it has filled.
        # Keys enter rotated at their own positions and a softmax
        # needs no order, so the ragged kernel reads the ring as a
        # linear cache of that length.
        plan = self._kernel_plan(q, cached_k, scale_k, S, ring=True)
        if plan:
            return self._kernel_step(plan, q, k, v, cached_k, cached_v,
                                     index, i, i % W,
                                     jnp.minimum(i + 1, W))
        # The dense branch (the oracle, and every S > 1 chunk): attend
        # [ring ++ block] BEFORE writing - a same-call write could
        # evict the oldest key still inside an earlier query row's
        # band. Slot s currently holds the newest position <= i-1
        # congruent to s mod W (negative = never written). A block
        # longer than the ring is whole in the second part, so a
        # chunk that laps the ring is still exact (`_cache_write`
        # then keeps its last W keys).
        s_idx = jnp.arange(W, dtype=i.dtype)
        last = i - 1
        slot_pos = last - ((last - s_idx) % W)
        valid = (i > 0) & (slot_pos >= 0)
        qpos = i + jnp.arange(S, dtype=i.dtype)
        kv_pos = jnp.concatenate([slot_pos, qpos])       # cache ++ block
        keep = banded_causal_mask(qpos, kv_pos, self.window)
        keep &= jnp.concatenate(
            [valid, jnp.ones((S,), bool)])[None, :]
        key = jnp.concatenate(
            [self._cache_read(cached_k, scale_k), k], axis=-3)
        val = jnp.concatenate(
            [self._cache_read(cached_v, scale_v), v], axis=-3)
        out = dot_product_attention(q, self._repeat_kv(key),
                                    self._repeat_kv(val),
                                    keep[None, None])
        self._cache_write(cached_k, cached_v, scale_k, scale_v,
                          index, k, v, i, S if count is None else count,
                          W)
        return out


def append_rows(buf, rows, i, count=None, *, axis):
    """``rows`` [.., S, ..] into ``buf`` [.., W, ..] (S <= W) at
    positions i .. i + S - 1 of ``axis``: the append of a cache that
    grows by position (K/V, their scales, latent rows).

    ``count`` None: every row, one `lax.dynamic_update_slice` - whose
    start CLAMPS to W - S, so the caller keeps i + S <= W. With a
    traced ``count`` (a chunk whose tail is pad) only the first
    ``count`` rows are written and every other row of ``buf`` keeps
    what it held - also where i + S passes W: the window that is read,
    merged and written back is the clamped one, and the rows are
    rolled to where they belong in it, so a prompt that ends within S
    rows of the cache's end lands on its own positions and not on the
    real rows below them."""
    axis %= buf.ndim
    if count is None:
        z = jnp.zeros((), i.dtype)
        return lax.dynamic_update_slice(
            buf, rows, [i if a == axis else z for a in range(buf.ndim)])
    S, W = rows.shape[axis], buf.shape[axis]
    # (`lax` by hand: this runs twice a layer at trace time, and a
    # `jnp.roll` by a traced shift alone cost a quarter of a chunk
    # program's trace)
    start = lax.min(i, np.asarray(W - S, i.dtype))
    shift = i - start                   # > 0 only in the last S rows
    r = lax.iota(i.dtype, S) - shift    # window row -> chunk row
    own = lax.broadcast_in_dim((r >= 0) & (r < count), rows.shape,
                               (axis,))
    held = lax.dynamic_slice_in_dim(buf, start, S, axis)
    rolled = lax.dynamic_slice_in_dim(      # = roll(rows, shift, axis)
        lax.concatenate([rows, rows], axis), S - shift, S, axis)
    return lax.dynamic_update_slice_in_dim(
        buf, lax.select(own, rolled, held), start, axis)


def _mesh_is_trivial() -> bool:
    """True when no ambient mesh (or an all-size-1 one) is installed —
    the condition under which a bare pallas_call needs no GSPMD
    partitioning rule."""
    from horovod_tpu.parallel.mesh import abstract_mesh
    mesh = abstract_mesh()
    return (mesh is None or mesh.empty
            or all(s == 1 for s in mesh.shape.values()))


def _kv_quantize(t: jax.Array):
    """Symmetric int8 over the head_dim: one f32 scale per
    (..., position, head) — the KV-cache codec (`kv_quant="int8"`).
    Delegates to the single tested codec in `ops.quantization`
    (same scale rule, clipping, and half-step error bound)."""
    from horovod_tpu.ops.quantization import quantize_int8
    return quantize_int8(t, axis=-1)


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One rotary rule, as a layer's attention applies it: the base,
    how much of the head turns, and - with ``yarn_factor`` - YaRN's
    frequencies (Peng et al. 2023, as `transformers` computes them:
    `truncate` on) and the scale on cos and sin.

    The first ``fraction * head_dim`` dimensions of q and k are rotated
    (half-split pairs inside that part), the rest pass through. Plain:
    ``inv_freq_j = theta^(-2j/d_r)``. YaRN: dimensions that turn more
    than ``beta_fast`` times over the original length keep that
    frequency, those that turn less than ``beta_slow`` times have it
    divided by the factor, a linear ramp between."""
    theta: float = 10000.0
    fraction: float = 1.0
    yarn_factor: Optional[float] = None
    yarn_original_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    scale: float = 1.0      # YaRN's attention_factor, on cos and sin

    def rotary_dim(self, head_dim: int) -> int:
        d_r = int(head_dim * self.fraction)
        if d_r < 2 or d_r % 2 or d_r > head_dim:
            raise ValueError(
                f"rotary part {self.fraction} of a head of {head_dim} "
                f"is {d_r} dimensions: need an even count in [2, D]")
        return d_r

    def yarn_ramp(self, head_dim: int):
        """(low, high) of the ramp over the frequency index."""
        d_r = self.rotary_dim(head_dim)

        def turns(n):       # the index that turns n times over L0
            return (d_r * math.log(self.yarn_original_len
                                   / (n * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        return (max(math.floor(turns(self.yarn_beta_fast)), 0),
                min(math.ceil(turns(self.yarn_beta_slow)), d_r - 1))

    def inv_freq(self, head_dim: int) -> np.ndarray:
        """float64 [d_r / 2]."""
        d_r = self.rotary_dim(head_dim)
        j = np.arange(d_r // 2, dtype=np.float64)
        plain = float(self.theta) ** (-2.0 * j / d_r)
        if self.yarn_factor is None:
            return plain
        low, high = self.yarn_ramp(head_dim)
        ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
        return plain * ((1.0 - ramp) + ramp / self.yarn_factor)

    def rotation(self, head_dim: int) -> dict:
        """`apply_rope`'s keywords for a head of ``head_dim``."""
        if (self.fraction == 1.0 and self.yarn_factor is None
                and self.scale == 1.0):
            return {"theta": self.theta}    # the rule as it always was
        return {"rotary_dim": self.rotary_dim(head_dim),
                "inv_freq": (None if self.yarn_factor is None else
                             tuple(self.inv_freq(head_dim))),
                "theta": self.theta, "scale": self.scale}


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0, *,
               rotary_dim: Optional[int] = None,
               inv_freq=None, scale: float = 1.0,
               interleaved: bool = False) -> jax.Array:
    """Rotary position embedding (Su et al. 2021), half-split layout
    (``interleaved``: pairs (2j, 2j + 1) instead, the layout of the
    DeepSeek family's latent attention; the result keeps its pairs
    where they were).

    ``x`` [..., S, H, D] with D even; ``positions`` [S] absolute token
    positions. Rotation is applied before the attention kernel at the
    LOGICAL level, so it composes unchanged with GSPMD sequence
    parallelism (ring/Ulysses shard the rotated tensors) and with the
    KV cache (keys are cached post-rotation at their absolute
    position).

    ``rotary_dim`` d_r < D rotates the first d_r dimensions (pairs j,
    j + d_r/2) and passes the rest through; ``inv_freq`` [d_r/2] gives
    the frequencies (None: ``theta^(-2j/d_r)``); ``scale`` multiplies
    cos and sin (`RopeSpec`).
    """
    d_r = x.shape[-1] if rotary_dim is None else int(rotary_dim)
    half = d_r // 2
    if inv_freq is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[:, None].astype(jnp.float32) * freqs   # [S, half]
    cos = jnp.cos(angles)[:, None, :]                          # [S, 1, h]
    sin = jnp.sin(angles)[:, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    if interleaved:
        x1, x2 = x[..., 0:d_r:2], x[..., 1:d_r:2]
        parts = [jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).reshape(*x.shape[:-1], d_r)]
    else:
        x1, x2 = x[..., :half], x[..., half:d_r]
        parts = [x1 * cos - x2 * sin, x1 * sin + x2 * cos]
    if d_r < x.shape[-1]:
        parts.append(x[..., d_r:].astype(parts[0].dtype))
    return jnp.concatenate(parts, axis=-1).astype(x.dtype)


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          mask: Optional[jax.Array] = None) -> jax.Array:
    """Plain softmax attention, [..., seq, heads, head_dim] layout.

    The numerically-stable baseline the blockwise/ring/Pallas kernels are
    tested against.
    """
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("...qhd,...khd->...hqk", q * scale, k)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("...hqk,...khd->...qhd", probs, v)


# ---------------------------------------------------------------------------
# Param sharding utilities.
# ---------------------------------------------------------------------------

def param_specs(variables) -> Any:
    """PartitionSpec pytree from the `nn.Partitioned` metadata (replicated
    P() for unannotated leaves)."""
    return nn.get_partition_spec(variables)


def shard_params(mesh, variables):
    """Place (possibly host-local) params onto the mesh per their
    annotations — the TP analogue of `broadcast_global_variables`."""
    from horovod_tpu.parallel.mesh import _place
    specs = param_specs(variables)
    return jax.tree.map(
        lambda x, s: _place(x, NamedSharding(mesh, s)),
        unbox(variables), specs)


def unbox(variables):
    """Strip `nn.Partitioned` boxes (plain arrays for optimizers that
    don't traverse metadata).

    Unlike `nn.meta.unbox`, never applies sharding constraints — flax's
    `Partitioned.unbox()` constrains the value when a mesh context is
    active, which rejects host/single-device arrays about to be
    re-placed by `shard_params`.
    """
    def strip(x):
        if isinstance(x, nn.meta.AxisMetadata):
            return getattr(x, "value", None) if hasattr(x, "value") \
                else x.unbox()
        return x
    return jax.tree.map(
        strip, variables,
        is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata))
