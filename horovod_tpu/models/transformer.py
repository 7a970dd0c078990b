"""Flagship model: GPT-style transformer LM over the full 5-axis mesh.

No reference equivalent — Horovod v0.10 ships no model library and no
attention (SURVEY §5.7); its largest exercised model family is the
tf_cnn_benchmarks CNNs. This is the TPU-native extension that makes the
brief's long-context + multi-axis parallelism first-class, composing
every `horovod_tpu.parallel` primitive in one model:

* **TP**: `ParallelSelfAttention` / `ParallelMLP` (Megatron column/row
  pairs, heads sharded over ``model``) — one all-reduce per sub-block,
  inserted by GSPMD, riding the innermost ICI axis.
* **SP**: `attn_impl="ring"` / `"ulysses"` run the attention as a
  shard_map region over the ``seq`` axis (K/V `ppermute` ring or
  all-to-all head swap).
* **EP**: `moe_every=n` replaces every n-th MLP with a GShard-style
  `MoELayer`, experts sharded over ``expert``.
* **DP**: the train step shards the batch over ``data``; since params
  carry no ``data`` axis, GSPMD inserts the gradient all-reduce —
  the reference's entire product (`DistributedOptimizer`,
  `horovod/tensorflow/__init__.py:127-186`) falls out of the sharding.
* **PP**: `TransformerBlockStack` exposes the per-block apply used by
  `parallel.pipeline.pipeline_apply_gspmd` (GPipe over ``pipe``).

Attention kernels: ``dot`` (materialized softmax baseline), ``blockwise``
(online-softmax scan), ``flash`` (Pallas TPU kernel,
`ops/flash_attention.py`), ``ring``/``ulysses`` (sequence-parallel).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

import flax.linen as nn

from horovod_tpu.annotations import hot_path
from horovod_tpu.parallel.expert import HeldExpertsMoE, MoELayer
from horovod_tpu.parallel.latent_attention import (
    LatentAttention, LatentSpec, latent_decode_plan,
)
from horovod_tpu.parallel.linear_attention import KDAAttention
from horovod_tpu.parallel.state_space import Mamba2Mixer, SsmSpec
from horovod_tpu.parallel.mesh import (
    AXIS_DATA, AXIS_MODEL, AXIS_SEQ, constrain, use,
)
from horovod_tpu.parallel.sequence import (
    banded_causal_mask, blockwise_attention, ring_attention_gspmd,
    ulysses_attention_gspmd,
)
from horovod_tpu.parallel.tensor import (
    ParallelMLP, ParallelSelfAttention, ParallelSwiGLU, RopeSpec,
    dot_product_attention,
    param_specs, shard_params, unbox,
)

Dtype = Any

ATTN_IMPLS = ("dot", "blockwise", "flash", "ring", "ring_flash",
              "ulysses", "ulysses_flash")

# A layer's token mixer (`TransformerLM.layer_kinds`): softmax attention
# under the flax scope "attn" or - the kind that a model with two kinds
# of softmax layer gives its sliding-window layers - "swa", the
# delta-rule linear attention "kda", latent attention "mla"
# (`parallel.latent_attention`: a softmax over heads too, but its cache
# holds head-less latent rows, so it is no member of SOFTMAX_KINDS -
# nothing that speaks of K/V heads, windows or `AttnSpec` applies), and
# the scalar-decay state-space layer "ssm" (`parallel.state_space`).
SOFTMAX_KINDS = ("attn", "swa")
LAYER_KINDS = SOFTMAX_KINDS + ("kda", "mla", "ssm")
# The kinds whose decode cache is a state that every step OVERWRITES,
# each with the layer that says which of its cache variables those are
# (`OVERWRITTEN`) and which of them its in-place step keeps itself for
# a lane that does not advance (`KEPT_BY_KERNEL`): THE predicate behind
# `has_recurrent_state`, `recurrent_leaf` and the tick's freeze.
RECURRENT_LAYERS = {"kda": KDAAttention, "ssm": Mamba2Mixer}
RECURRENT_KINDS = tuple(RECURRENT_LAYERS)


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """What one KIND of softmax layer has of its own where a model's
    kinds differ (`TransformerLM.attn_specs`): the query heads
    (None = the model's ``num_heads``), the sliding window (as given:
    None = full attention, a kind that brings a spec states its
    window), the rotary rule (None = the model's ``rope_theta``
    over the whole head) and the scale of the scores before the
    softmax (None = head_dim ** -0.5). K/V heads and the head size
    are the model's."""
    num_heads: Optional[int] = None
    window: Optional[int] = None
    rope: Optional[RopeSpec] = None
    scale: Optional[float] = None

# The LLaMA-family knob set — single source for `compat.hf.from_hf_llama`
# and the driver dryrun's llama leg, so the two can never silently
# diverge.
LLAMA_ARCH_KW = dict(norm="rmsnorm", mlp_impl="swiglu",
                     tied_head=False)


def make_attn_fn(impl: str, *, causal: bool = True,
                 block_size: int = 512,
                 window: Optional[int] = None) -> Optional[Callable]:
    """attn_fn for `ParallelSelfAttention` (None = dot baseline, which
    consumes the explicit mask argument instead). ``window`` = sliding
    -window attention (last `window` positions only; requires causal).
    ``impl="flash"`` runs the Pallas kernel at the tiles it chooses
    from the shape (`ops.flash_attention._pick_tiles`).
    """
    from horovod_tpu.parallel.sequence import check_window
    check_window(window)
    if impl == "dot":
        return None

    def _no_mask(m):
        if m is not None:
            raise NotImplementedError(
                f"attn_impl={impl!r} supports causal masking only; use "
                f"impl='dot' for arbitrary masks")

    if impl == "blockwise":
        def attn(q, k, v, m):
            _no_mask(m)
            return blockwise_attention(q, k, v, causal=causal,
                                       window=window,
                                       block_size=block_size)
        return attn
    if impl == "flash":
        from horovod_tpu.ops.flash_attention import flash_attention

        kernel = functools.partial(
            flash_attention, causal=causal, window=window)

        def attn(q, k, v, m):
            _no_mask(m)
            # A Mosaic kernel is opaque to the GSPMD partitioner (on
            # the chip: "Mosaic kernels cannot be automatically
            # partitioned" — interpret mode on the CPU never says
            # so). Under a mesh GSPMD still shards over, hand each
            # device its (batch, heads) block through shard_map; the
            # sequence stays whole (ring_flash / ulysses_flash are
            # the impls that split it).
            from horovod_tpu.parallel.mesh import (abstract_mesh,
                                                   auto_axis_names)
            mesh = abstract_mesh()
            sizes = ({} if mesh.empty else
                     {n: mesh.shape[n] for n in auto_axis_names(mesh)
                      if mesh.shape[n] > 1})
            if not sizes:
                return kernel(q, k, v)

            def fits(axis, *dims):
                ok = axis in sizes and not any(d % sizes[axis]
                                               for d in dims)
                return axis if ok else None

            spec = P(fits(AXIS_DATA, q.shape[0]), None,
                     fits(AXIS_MODEL, q.shape[2], k.shape[2]), None)
            return jax.shard_map(kernel, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec)(q, k, v)
        # The kernel consumes grouped K/V natively (index-mapped kv
        # heads); let ParallelSelfAttention skip the repeat.
        attn.native_gqa = True
        return attn
    if impl in ("ring", "ring_flash", "ulysses", "ulysses_flash"):
        if impl == "ulysses":
            sp_fn = ulysses_attention_gspmd
        elif impl == "ulysses_flash":
            # Local attention after the head-swap all_to_alls is the
            # Pallas flash kernel instead of the blockwise scan.
            from horovod_tpu.ops.flash_attention import flash_attention
            sp_fn = functools.partial(ulysses_attention_gspmd,
                                      attn_impl=flash_attention)
        elif impl == "ring_flash":
            # Pallas flash kernel on every ring rotation; partials
            # merge by logsumexp (sequence._ring_attention_flash).
            sp_fn = functools.partial(ring_attention_gspmd,
                                      block_impl="flash")
        else:
            sp_fn = ring_attention_gspmd

        native_gqa = impl in ("ring_flash", "ulysses_flash")

        def attn(q, k, v, m):
            _no_mask(m)
            # Off-mesh (e.g. model.init, single-device eval) there is no
            # seq axis to ring over; blockwise is the same math locally
            # and attention has no params, so the init trace is identical.
            from horovod_tpu.parallel.mesh import abstract_mesh
            mesh = abstract_mesh()
            if mesh is None or mesh.empty:
                if native_gqa and k.shape[2] != q.shape[2]:
                    # The flash paths take grouped K/V natively; the
                    # blockwise fallback needs the repeat inline.
                    g = q.shape[2] // k.shape[2]
                    k = jnp.repeat(k, g, axis=2)
                    v = jnp.repeat(v, g, axis=2)
                return blockwise_attention(q, k, v, causal=causal,
                                           window=window,
                                           block_size=block_size)
            return sp_fn(None, q, k, v, causal=causal, window=window)

        # K/V stay at kv-head width through the ppermute hops /
        # all_to_alls — 1/group the ICI payload (the kernel index-maps
        # kv heads; see flash_attention.native_gqa).
        attn.native_gqa = native_gqa
        return attn
    raise ValueError(f"attn_impl must be one of {ATTN_IMPLS}, got {impl!r}")


def _make_norm(kind: str, dtype, eps: float, name: str):
    """The block's norm: LayerNorm (GPT family) or RMSNorm (LLaMA
    family — scale only, no bias/mean-centering)."""
    if kind == "layernorm":
        return nn.LayerNorm(dtype=dtype, epsilon=eps, name=name)
    if kind == "rmsnorm":
        return nn.RMSNorm(dtype=dtype, epsilon=eps, name=name)
    raise ValueError(f"norm must be layernorm|rmsnorm, got {kind!r}")


class TransformerBlock(nn.Module):
    """Pre-LN transformer block: a token mixer (``mixer``: softmax
    attention, delta-rule linear attention or latent attention), then
    an MLP (dense, or one of the two expert layers) - in line::

        x1 = x  + Mixer(ln_attn(x));   y = x1 + FFN(ln_mlp(x1))

    ``shortcut_moe`` (LongCat-Flash's shortcut-connected expert layer)
    makes the block that model's LAYER instead - two mixers and two
    dense MLPs in line, and the expert layer computed from the first
    MLP's normed input but added only at the end, so that nothing
    between waits for it::

        x1 = x  + Mixer_0(ln_attn_0(x))
        h  = ln_mlp_0(x1);  s = MoE(h)
        x2 = x1 + FFN_0(h)
        x3 = x2 + Mixer_1(ln_attn_1(x2))
        x4 = x3 + FFN_1(ln_mlp_1(x3));   y = x4 + s

    under the scopes ``<mixer>_0`` / ``<mixer>_1``, ``mlp_0`` /
    ``mlp_1`` and ``moe``: two caches a block."""

    num_heads: int
    head_dim: int
    num_kv_heads: Optional[int] = None
    pos_emb: str = "none"        # "none" | "rope"
    rope_theta: float = 10000.0
    rope: Optional[RopeSpec] = None   # see ParallelSelfAttention.rope
    window: Optional[int] = None  # sliding-window attention
    mlp_ratio: int = 4
    dtype: Optional[Dtype] = jnp.bfloat16
    attn_impl: str = "blockwise"
    moe: bool = False
    num_experts: int = 8
    moe_k: int = 2
    moe_capacity_factor: float = 1.25
    decode: bool = False
    chunked_prefill: bool = False   # see ParallelSelfAttention
    # Linear-cache decode reads the filled prefix in slices this big
    # (see ParallelSelfAttention.decode_prefix_block); 0/None = the
    # cache-wide-mask path.
    decode_prefix_block: Optional[int] = 256
    # None = the code chooses (ops.flash_attention.decode_attention_plan)
    decode_prefix_impl: Optional[str] = None   # | "lax" | "pallas"
    causal: bool = True     # False = bidirectional (encoder / ViT)
    weight_quant: Optional[str] = None   # None | "int8" (block matmuls)
    kv_quant: Optional[str] = None       # None | "int8" (decode cache)
    attn_bias: bool = False              # GPT-2-family checkpoints
    attn_out_bias: Optional[bool] = None  # None = follow attn_bias
    ln_eps: float = 1e-6
    norm: str = "layernorm"              # "layernorm" | "rmsnorm"
    mlp_impl: str = "gelu"               # "gelu" | "swiglu" (LLaMA)
    mlp_hidden: Optional[int] = None     # absolute width (else ratio*d)
    lora_rank: int = 0                   # LoRA adapters on the Denses
    lora_alpha: Optional[float] = None
    # The token mixer (`LAYER_KINDS`): "attn" | "swa" (softmax
    # attention; the name is the flax scope, so that a model with two
    # kinds of softmax layer tells them apart in its parameters and
    # its trace) | "kda" (delta-rule linear attention,
    # `parallel.linear_attention.KDAAttention`).
    mixer: str = "attn"
    # sigmoid output gate (attn): True a channel, "head" a head
    attn_gate: Union[bool, str] = False
    # "gshard" (`MoELayer`: capacity, drops) | "dropless"
    # (`HeldExpertsMoE`: the experts this chip holds, no drops).
    moe_impl: str = "gshard"
    moe_hidden: Optional[int] = None     # expert width (else ratio*d)
    moe_held: Optional[Tuple[int, int]] = None   # (first, count)
    moe_shared_hidden: int = 0
    moe_router: str = "sigmoid"          # see HeldExpertsMoE.router
    moe_scale: float = 1.0
    moe_zero_experts: int = 0            # HeldExpertsMoE.zero_experts
    moe_normalize: bool = True           # HeldExpertsMoE.normalize
    moe_router_bias: Optional[bool] = None
    moe_groups: Optional[Tuple[int, int]] = None  # HeldExpertsMoE.groups
    latent: Optional[LatentSpec] = None  # the widths of an "mla" mixer
    kda_neg_eigval: bool = True      # KDAAttention.allow_neg_eigval
    ssm: Optional[SsmSpec] = None        # the widths of an "ssm" mixer
    shortcut_moe: bool = False           # see the docstring
    softmax_scale: Optional[float] = None    # see `AttnSpec.scale`
    # Both branches are multiplied by this before they are added to
    # the residual stream (`TransformerLM.residual_scale`); None = 1.
    residual_scale: Optional[float] = None

    @nn.compact
    def __call__(self, x: jax.Array,
                 advance: Optional[jax.Array] = None,
                 count: Optional[jax.Array] = None) -> jax.Array:
        # ``count``: `TransformerLM.__call__`'s, handed to every
        # sublayer that keeps a cache or routes tokens
        d = x.shape[-1]
        if self.mixer not in LAYER_KINDS:
            raise ValueError(
                f"mixer must be one of {LAYER_KINDS}, got "
                f"{self.mixer!r}")
        if self.window is not None and not self.causal:
            # Every masked impl raises this from inside its scan; the
            # dot baseline would silently drop the window instead —
            # make the contract uniform and early.
            raise ValueError(
                "window (sliding-window attention) requires "
                "causal=True; bidirectional windowed attention is not "
                "implemented")
        # Decode ticks (S=1) attend against the KV cache inside the
        # attention module; the attn_fn (flash/ring/...) is used by the
        # ONE-PASS PREFILL (S>1 from an empty cache), which is plain
        # causal attention over the prompt block — flash-able.
        attn_fn = make_attn_fn(self.attn_impl, causal=self.causal,
                               window=self.window)
        mask = None
        if attn_fn is None and not self.decode and self.causal:
            # dot baseline materializes the banded causal mask
            # (bidirectional attention = no mask at all)
            S = x.shape[-2]
            pos = jnp.arange(S)
            mask = banded_causal_mask(pos, pos, self.window)[None, None]

        def norm(name):
            return _make_norm(self.norm, self.dtype, self.ln_eps, name)

        def mix(h, name):
            if self.mixer == "kda":
                return KDAAttention(
                    num_heads=self.num_heads, head_dim=self.head_dim,
                    out_features=d, norm_eps=self.ln_eps,
                    dtype=self.dtype, decode=self.decode,
                    allow_neg_eigval=self.kda_neg_eigval, name=name)(
                    h, advance, count)
            if self.mixer == "ssm":
                if self.ssm is None:
                    raise ValueError("an 'ssm' mixer needs `ssm`, its "
                                     "SsmSpec")
                return Mamba2Mixer(
                    spec=self.ssm, out_features=d, norm_eps=self.ln_eps,
                    dtype=self.dtype, decode=self.decode, name=name)(
                    h, advance, count)
            if self.mixer == "mla":
                if self.latent is None:
                    raise ValueError("an 'mla' mixer needs `latent`, "
                                     "its LatentSpec")
                return LatentAttention(
                    num_heads=self.num_heads, spec=self.latent,
                    out_features=d, rope_theta=self.rope_theta,
                    norm_eps=self.ln_eps, dtype=self.dtype,
                    decode=self.decode,
                    chunked_prefill=self.chunked_prefill,
                    decode_prefix_block=self.decode_prefix_block,
                    decode_prefix_impl=self.decode_prefix_impl,
                    name=name)(h, count)
            return ParallelSelfAttention(
                num_heads=self.num_heads, head_dim=self.head_dim,
                num_kv_heads=self.num_kv_heads, pos_emb=self.pos_emb,
                rope_theta=self.rope_theta, rope=self.rope,
                window=self.window, softmax_scale=self.softmax_scale,
                dtype=self.dtype, attn_fn=attn_fn, decode=self.decode,
                chunked_prefill=self.chunked_prefill,
                decode_prefix_block=self.decode_prefix_block,
                decode_prefix_impl=self.decode_prefix_impl,
                weight_quant=self.weight_quant,
                kv_quant=self.kv_quant,
                use_bias=self.attn_bias, out_bias=self.attn_out_bias,
                out_gate=self.attn_gate,
                out_features=(None if d == self.num_heads * self.head_dim
                              else d),
                lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
                name=name)(h, mask, count)

        def experts(h):
            if self.moe_impl == "dropless":
                return HeldExpertsMoE(
                    num_experts=self.num_experts,
                    hidden=self.moe_hidden or self.mlp_ratio * d,
                    k=self.moe_k, held=self.moe_held,
                    shared_hidden=self.moe_shared_hidden,
                    router=self.moe_router, scale=self.moe_scale,
                    zero_experts=self.moe_zero_experts,
                    normalize=self.moe_normalize,
                    router_bias=self.moe_router_bias,
                    groups=self.moe_groups,
                    dtype=self.dtype, name="moe")(h, count)
            if self.moe_impl == "gshard":
                return MoELayer(
                    num_experts=self.num_experts,
                    hidden=self.moe_hidden or self.mlp_ratio * d,
                    k=self.moe_k,
                    capacity_factor=self.moe_capacity_factor,
                    dtype=self.dtype, name="moe")(h, count)
            raise ValueError(
                f"moe_impl must be gshard|dropless, got "
                f"{self.moe_impl!r}")

        def dense(h, name):
            hidden = self.mlp_hidden or self.mlp_ratio * d
            if self.mlp_impl in ("swiglu", "geglu"):
                # Same gated two-projection block; geglu (Gemma) gates
                # with tanh-gelu instead of silu.
                return ParallelSwiGLU(
                    hidden=hidden, out=d,
                    activation=("gelu_tanh" if self.mlp_impl == "geglu"
                                else "silu"),
                    weight_quant=self.weight_quant,
                    lora_rank=self.lora_rank,
                    lora_alpha=self.lora_alpha,
                    dtype=self.dtype, name=name)(h)
            if self.mlp_impl == "gelu":
                return ParallelMLP(hidden=hidden, out=d,
                                   weight_quant=self.weight_quant,
                                   lora_rank=self.lora_rank,
                                   lora_alpha=self.lora_alpha,
                                   dtype=self.dtype, name=name)(h)
            raise ValueError(
                f"mlp_impl must be gelu|swiglu|geglu, got "
                f"{self.mlp_impl!r}")

        def scaled(branch):
            if self.residual_scale is None:
                return branch
            return branch * jnp.asarray(self.residual_scale, x.dtype)

        if self.shortcut_moe:
            if not self.moe:
                raise ValueError("shortcut_moe is a block WITH an "
                                 "expert layer (moe=True)")
            if self.residual_scale is not None:
                raise ValueError("residual_scale is not defined for a "
                                 "shortcut_moe block")
            x = x + mix(norm("ln_attn_0")(x), self.mixer + "_0")
            h = norm("ln_mlp_0")(x)
            shortcut = experts(h)
            x = x + dense(h, "mlp_0")
            x = x + mix(norm("ln_attn_1")(x), self.mixer + "_1")
            x = x + dense(norm("ln_mlp_1")(x), "mlp_1")
            return x + shortcut
        x = x + scaled(mix(norm("ln_attn")(x), self.mixer))
        h = norm("ln_mlp")(x)
        return x + scaled(experts(h) if self.moe else dense(h, "mlp"))


class TransformerLM(nn.Module):
    """Decoder-only LM. Input [B, S] int tokens → [B, S, V] logits.

    Embedding table and LM head are vocab-sharded over ``model``
    (Megatron layout); activations are pinned (data, seq) so the batch
    and sequence axes stay distributed through every block.
    """

    vocab_size: int
    num_layers: int
    num_heads: int
    head_dim: int
    num_kv_heads: Optional[int] = None   # GQA: fewer K/V heads
    pos_emb: str = "learned"             # "learned" | "rope"
    rope_theta: float = 10000.0
    window: Optional[int] = None         # sliding-window attention
    mlp_ratio: int = 4
    max_len: int = 2048
    dtype: Optional[Dtype] = jnp.bfloat16
    attn_impl: str = "blockwise"
    moe_every: int = 0          # 0 = dense; n = every n-th block is MoE
    num_experts: int = 8
    moe_k: int = 2
    moe_capacity_factor: float = 1.25
    remat: bool = False
    decode: bool = False        # autoregressive inference w/ KV cache
    # S>1 decode calls append to a non-empty cache (general cache-wide
    # mask) instead of the one-pass empty-cache prefill; see
    # ParallelSelfAttention.chunked_prefill.
    chunked_prefill: bool = False
    # Linear-cache decode attention touches only the filled prefix, in
    # slices this big (ParallelSelfAttention.decode_prefix_block);
    # 0/None = cache-wide-mask path.
    decode_prefix_block: Optional[int] = 256
    # None = the code chooses (ops.flash_attention.decode_attention_plan)
    decode_prefix_impl: Optional[str] = None   # | "lax" | "pallas"
    # "int8": block matmul kernels stored int8 + per-channel scales
    # (weight-only, inference; `ops.quantization.quantize_lm_params`).
    # Embedding/head and LayerNorms stay full precision.
    weight_quant: Optional[str] = None
    # "int8": decode KV cache stored int8 with per-(position, head)
    # scales — 2x context length per byte of cache HBM.
    kv_quant: Optional[str] = None
    attn_bias: bool = False    # attention projection biases (GPT-2)
    attn_out_bias: Optional[bool] = None  # Qwen2: qkv bias, no out bias
    ln_eps: float = 1e-6       # LayerNorm epsilon (GPT-2: 1e-5)
    norm: str = "layernorm"    # "layernorm" | "rmsnorm" (LLaMA)
    mlp_impl: str = "gelu"     # "gelu" | "swiglu" (LLaMA)
    mlp_hidden: Optional[int] = None   # absolute MLP width override
    # False: a separate vocab-sharded lm_head param instead of reusing
    # the embedding (LLaMA-family default).
    tied_head: bool = True
    # Input embeddings multiplied by this after lookup (Gemma:
    # sqrt(hidden_size)); the tied LM head reads the UNSCALED table,
    # matching that family's convention. None = 1.
    embed_scale: Optional[float] = None
    # Granite's other multipliers, each None = 1 (and then no
    # instruction): every block's two branches are multiplied by
    # ``residual_scale`` before they join the residual stream, and the
    # logits are divided by ``logits_divisor`` - on the final norm's
    # output, the hidden state every head reads (`return_hidden` too),
    # so the divisor reaches the serving programs' heads and the fused
    # loss alike; a power of two is exact there in any dtype.
    residual_scale: Optional[float] = None
    logits_divisor: Optional[float] = None
    # LoRA (Hu et al. 2021): rank-r adapters on every block Dense;
    # train with `models.lora.lora_label_fn` masking the base frozen,
    # merge for serving with `models.lora.merge_lora`.
    lora_rank: int = 0
    lora_alpha: Optional[float] = None
    # Width of the residual stream; None = num_heads x head_dim.
    hidden_size: Optional[int] = None
    # Hybrid models: the token mixer of each layer, "attn" | "swa" |
    # "kda" | "mla" | "ssm" (`LAYER_KINDS`; len == num_layers); None =
    # "attn" everywhere. A "kda" layer keeps a recurrent state in the
    # decode cache, not K/V (`parallel.linear_attention`), and so does
    # an "ssm" layer (`parallel.state_space`), at the widths of
    # ``ssm``; "swa" is a second kind of softmax layer, under its own
    # scope; an "mla" layer keeps head-less latent rows
    # (`parallel.latent_attention`), at the widths of ``latent`` - which
    # also carries that layer's rotary rule and softmax factor.
    layer_kinds: Optional[Tuple[str, ...]] = None
    latent: Optional[LatentSpec] = None
    ssm: Optional[SsmSpec] = None
    # A "kda" layer's beta: 2 sigmoid in (0, 2) (the published
    # `allow_neg_eigval`), or with False sigmoid in (0, 1).
    kda_neg_eigval: bool = True
    # What a kind of softmax layer has of its own: ((kind, AttnSpec),
    # ...). A kind without an entry takes the model-wide ``num_heads``
    # / ``window`` / ``rope_theta``.
    attn_specs: Optional[Tuple[Tuple[str, AttnSpec], ...]] = None
    # sigmoid output gate (attn): True a channel, "head" a head
    attn_gate: Union[bool, str] = False
    # The expert layer of the `moe_every`-th blocks: "gshard"
    # (`MoELayer`) | "dropless" (`HeldExpertsMoE`: routes over all
    # `num_experts`, computes the `moe_held` = (first, count) it holds,
    # plus a shared expert of width `moe_shared_hidden`).
    moe_impl: str = "gshard"
    moe_hidden: Optional[int] = None
    moe_held: Optional[Tuple[int, int]] = None
    moe_shared_hidden: int = 0
    moe_router: str = "sigmoid"     # `HeldExpertsMoE.router`
    moe_scale: float = 1.0          # `HeldExpertsMoE.scale`
    moe_zero_experts: int = 0       # `HeldExpertsMoE.zero_experts`
    moe_normalize: bool = True      # `HeldExpertsMoE.normalize`
    moe_router_bias: Optional[bool] = None
    # (n_group, topk_group): the choice limited to groups
    # (`HeldExpertsMoE.groups`, the DeepSeek-V3 family's gate)
    moe_groups: Optional[Tuple[int, int]] = None
    # Every layer is LongCat-Flash's: two mixers, two dense MLPs and a
    # shortcut-connected expert layer (`TransformerBlock.shortcut_moe`;
    # with ``moe_every=1``) - so a layer has TWO caches.
    moe_shortcut: bool = False
    # Layers whose MLP stays dense whatever ``moe_every`` says (the
    # published `mlp_only_layers`: a leading dense layer is (0,)).
    mlp_only_layers: Tuple[int, ...] = ()

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's token mixer."""
        kinds = tuple(self.layer_kinds or ("attn",) * self.num_layers)
        if len(kinds) != self.num_layers:
            raise ValueError(
                f"layer_kinds has {len(kinds)} entries for "
                f"{self.num_layers} layers")
        bad = sorted(set(kinds) - set(LAYER_KINDS))
        if bad:
            raise ValueError(
                f"layer_kinds must be of {LAYER_KINDS}, got {bad}")
        return kinds

    @property
    def softmax_kinds(self) -> Tuple[str, ...]:
        """The kinds of softmax layer this model has, in the order
        they first appear."""
        return tuple(k for k in dict.fromkeys(self.kinds)
                     if k in SOFTMAX_KINDS)

    def attn_spec(self, kind: str) -> AttnSpec:
        """THE expression of "what kind of attention is this layer":
        the heads, window and rotary rule of the softmax layers of
        ``kind``, the kind's own entry of ``attn_specs`` over the
        model-wide values."""
        if kind not in SOFTMAX_KINDS:
            raise ValueError(f"{kind!r} is no softmax kind")
        own = dict(self.attn_specs or ()).get(kind)
        spec = AttnSpec(
            num_heads=(own and own.num_heads) or self.num_heads,
            window=own.window if own else self.window,
            rope=(own and own.rope) or RopeSpec(theta=self.rope_theta),
            scale=own.scale if own else None)
        if kind == "swa" and spec.window is None:
            raise ValueError(
                "a 'swa' layer needs a window: give the kind an "
                "AttnSpec(window=...) or the model a window")
        return spec

    @property
    def has_latent_cache(self) -> bool:
        """True when some layer's decode cache holds latent rows
        without a head axis (an "mla" layer): appended to like K/V,
        but with no heads to shard and no block form yet."""
        return "mla" in (self.layer_kinds or ())

    @property
    def has_recurrent_state(self) -> bool:
        """True when some layer's decode cache is a state that each
        step overwrites (no K/V rows to graft, page or rewind)."""
        return any(k in RECURRENT_KINDS
                   for k in self.layer_kinds or ())

    @property
    def recurrent_kinds(self) -> Tuple[str, ...]:
        """The kinds of recurrent layer this model has, in the order
        they first appear."""
        return tuple(k for k in dict.fromkeys(self.layer_kinds or ())
                     if k in RECURRENT_KINDS)

    @property
    def rolling_window(self) -> Optional[int]:
        """The slots of the rolling caches (the widest, should the
        kinds differ); None when no layer has a sliding window."""
        windows = [self.attn_spec(k).window for k in self.softmax_kinds]
        return max((w for w in windows if w is not None), default=None)

    @property
    def has_rolling_cache(self) -> bool:
        """True when some layer's decode cache is a ring of `window`
        slots (slot = position mod window): K/V rows that later
        positions overwrite in place - no block-aligned prefix to
        page, share or ship, no rewind."""
        return self.rolling_window is not None

    @property
    def context_unbounded(self) -> bool:
        """True when no cache bounds a sequence: rotary positions,
        and every softmax layer a ring. One full-attention layer (or a
        position table) bounds a request by ``max_len``."""
        kinds = self.softmax_kinds
        return (self.pos_emb == "rope" and bool(kinds) and all(
            self.attn_spec(k).window is not None for k in kinds))

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 return_hidden: bool = False,
                 advance: Optional[jax.Array] = None,
                 count: Optional[jax.Array] = None) -> Any:
        """``count`` (traced int32 in 1 .. S; decode mode, an S > 1
        chunk appended to the caches): only the first ``count``
        positions are the prompt's, the rest are pad. A pad position
        leaves every cache as it found it - no K/V or latent row, no
        ring slot, no decay of a recurrent state nor a write to it, no
        row of its convolution's tail, no index advanced - and is
        routed to no expert; what the model returns at a pad position
        is the caller's to discard (`slot_prefill_chunk` reads
        position ``count - 1``). None: every position is real, and the
        program is the one it was before the argument existed."""
        if self.pos_emb not in ("learned", "rope", "none"):
            raise ValueError(
                f"pos_emb must be 'learned', 'rope' or 'none', "
                f"got {self.pos_emb!r}")
        kinds = self.kinds
        B, S = tokens.shape
        d = self.hidden_size or self.num_heads * self.head_dim
        embed = self.param(
            "embed",
            nn.with_partitioning(nn.initializers.normal(0.02),
                                 (AXIS_MODEL, None)),
            (self.vocab_size, d), jnp.float32)
        x = jnp.take(embed, tokens, axis=0)
        if self.embed_scale is not None:
            x = x * jnp.asarray(self.embed_scale, x.dtype)
        if self.pos_emb == "learned":
            # Rotary positions live inside the attention (applied to
            # q/k at absolute positions — no learned table, no
            # position state outside the per-block KV cache index);
            # learned positions add a table slice here; "none" (NoPE)
            # adds nothing anywhere.
            pos = self.param("pos", nn.initializers.normal(0.02),
                             (self.max_len, d), jnp.float32)
            if self.decode:
                # Position comes from the running cache index, not the
                # input offset (tokens arrive one tick at a time).
                idx = self.variable("cache", "pos_index",
                                    lambda: jnp.zeros((), jnp.int32))
                if count is None:
                    p = lax.dynamic_slice_in_dim(pos, idx.value, S,
                                                 axis=0)
                else:
                    # by position: a slice that passes the table's end
                    # would be shifted down over the real positions
                    p = jnp.take(pos, idx.value + jnp.arange(S), axis=0,
                                 mode="clip")
                if not self.is_initializing():
                    idx.value = idx.value + (S if count is None
                                             else count)
            else:
                p = pos[:S]
            x = x + p
        x = x.astype(self.dtype)
        x = constrain(x, AXIS_DATA, AXIS_SEQ, None)

        block_cls = TransformerBlock
        if self.remat:
            block_cls = nn.remat(TransformerBlock)
        for i in range(self.num_layers):
            moe = (self.moe_every > 0 and (i + 1) % self.moe_every == 0
                   and i not in self.mlp_only_layers)
            # a recurrent layer has the model's heads and no positions
            spec = (self.attn_spec(kinds[i]) if kinds[i] in SOFTMAX_KINDS
                    else AttnSpec(num_heads=self.num_heads))
            x = block_cls(
                num_heads=spec.num_heads, head_dim=self.head_dim,
                num_kv_heads=self.num_kv_heads,
                pos_emb=("rope" if self.pos_emb == "rope" else "none"),
                rope_theta=self.rope_theta, rope=spec.rope,
                window=spec.window, softmax_scale=spec.scale,
                residual_scale=self.residual_scale,
                mlp_ratio=self.mlp_ratio, dtype=self.dtype,
                attn_impl=self.attn_impl, moe=moe,
                num_experts=self.num_experts, moe_k=self.moe_k,
                moe_capacity_factor=self.moe_capacity_factor,
                decode=self.decode,
                chunked_prefill=self.chunked_prefill,
                decode_prefix_block=self.decode_prefix_block,
                decode_prefix_impl=self.decode_prefix_impl,
                weight_quant=self.weight_quant,
                kv_quant=self.kv_quant,
                attn_bias=self.attn_bias,
                attn_out_bias=self.attn_out_bias,
                ln_eps=self.ln_eps,
                norm=self.norm, mlp_impl=self.mlp_impl,
                mlp_hidden=self.mlp_hidden,
                lora_rank=self.lora_rank,
                lora_alpha=self.lora_alpha,
                mixer=kinds[i], attn_gate=self.attn_gate,
                moe_impl=self.moe_impl,
                moe_hidden=self.moe_hidden, moe_held=self.moe_held,
                moe_shared_hidden=self.moe_shared_hidden,
                moe_router=self.moe_router, moe_scale=self.moe_scale,
                moe_zero_experts=self.moe_zero_experts,
                moe_normalize=self.moe_normalize,
                moe_router_bias=self.moe_router_bias,
                moe_groups=self.moe_groups,
                latent=self.latent, ssm=self.ssm,
                kda_neg_eigval=self.kda_neg_eigval,
                shortcut_moe=self.moe_shortcut,
                name=f"block_{i}")(
                # which lanes a decode step may move: a recurrent
                # layer's (`RECURRENT_LAYERS`), nobody else's
                *((x, advance) if kinds[i] in RECURRENT_KINDS
                  else (x,)),
                **({} if count is None else {"count": count}))
            x = constrain(x, AXIS_DATA, AXIS_SEQ, None)

        x = _make_norm(self.norm, self.dtype, self.ln_eps,
                       "ln_f")(x)
        if self.logits_divisor is not None:
            x = x * jnp.asarray(1.0 / self.logits_divisor, x.dtype)
        head = embed
        if not self.tied_head:
            head = self.param(
                "lm_head",
                nn.with_partitioning(nn.initializers.normal(0.02),
                                     (AXIS_MODEL, None)),
                (self.vocab_size, d), jnp.float32)
        if return_hidden:
            # For the chunked fused head+loss (`chunked_lm_loss`): the
            # [B, S, V] logits never materialize. `head` is the embed
            # when tied, the separate lm_head otherwise.
            return x, head
        # LM head (tied = the embedding): logits sharded over
        # ``model`` on vocab; the CE loss reduces over it with
        # GSPMD-inserted collectives.
        logits = jnp.einsum("bsd,vd->bsv", x,
                            head.astype(self.dtype))
        return constrain(logits, AXIS_DATA, AXIS_SEQ, AXIS_MODEL)


class TransformerBlockStack(nn.Module):
    """The per-stage body for pipeline parallelism: `layers_per_stage`
    blocks applied in sequence, no embedding/head (those live outside the
    pipeline loop). Used via `pipeline_apply_gspmd` with this module's
    params stacked [P, ...] over the ``pipe`` axis."""

    num_heads: int
    head_dim: int
    num_kv_heads: Optional[int] = None
    pos_emb: str = "none"        # "none" | "rope"
    rope_theta: float = 10000.0
    window: Optional[int] = None         # sliding-window attention
    layers_per_stage: int = 1
    mlp_ratio: int = 4
    dtype: Optional[Dtype] = jnp.bfloat16
    attn_impl: str = "blockwise"
    attn_bias: bool = False
    attn_out_bias: Optional[bool] = None
    ln_eps: float = 1e-6
    norm: str = "layernorm"
    mlp_impl: str = "gelu"
    mlp_hidden: Optional[int] = None
    lora_rank: int = 0
    lora_alpha: Optional[float] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        for i in range(self.layers_per_stage):
            x = TransformerBlock(
                num_heads=self.num_heads, head_dim=self.head_dim,
                num_kv_heads=self.num_kv_heads,
                pos_emb=self.pos_emb, rope_theta=self.rope_theta,
                window=self.window,
                mlp_ratio=self.mlp_ratio, dtype=self.dtype,
                attn_impl=self.attn_impl,
                attn_bias=self.attn_bias,
                attn_out_bias=self.attn_out_bias,
                ln_eps=self.ln_eps,
                norm=self.norm, mlp_impl=self.mlp_impl,
                mlp_hidden=self.mlp_hidden,
                lora_rank=self.lora_rank,
                lora_alpha=self.lora_alpha,
                name=f"block_{i}")(x)
        return x


# ---------------------------------------------------------------------------
# Train step (GSPMD: jit over the mesh; DP/TP/SP/EP collectives inserted
# by the partitioner from the param/activation shardings).
# ---------------------------------------------------------------------------

def lm_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token cross entropy, [B, S, V] logits vs [B, S] tokens."""
    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1].astype(jnp.float32), tokens[:, 1:]).mean()


def chunked_lm_loss(hidden: jax.Array, embed: jax.Array,
                    tokens: jax.Array, *, chunk: int = 512) -> jax.Array:
    """Next-token cross entropy fused with the LM head, scanned over
    sequence chunks so the [B, S, V] logits tensor never materializes.

    The plain path's logits are the LM's single biggest activation —
    1 GiB at B8·S2048·V32k bf16, and the dominant allocation in the
    OOM report that sank the blockwise config on a 16 GB chip. Here
    each scan tick computes [B, chunk, V] logits, folds them into the
    running CE sum, and `jax.checkpoint` recomputes them in the
    backward, so peak memory drops by S/chunk at the cost of one extra
    head matmul in the backward (a few % of total step FLOPs).

    Composes with dp (use via `make_lm_train_step(loss_chunk=...)`);
    with sequence parallelism keep the plain loss — the chunk reshape
    would fight the ``seq`` sharding of `hidden`. The batch must
    divide the ``data`` axis (the standard SPMD input contract — a
    ragged batch can trip an XLA partitioner CHECK inside the scan).
    """
    B, S, _ = hidden.shape
    P = S - 1
    total = chunked_weighted_ce(
        hidden[:, :-1], embed, tokens[:, 1:],
        jnp.ones((B, P), jnp.float32), chunk=chunk)
    return total / (B * P)


def chunked_weighted_ce(hidden: jax.Array, head: jax.Array,
                        targets: jax.Array, weights: jax.Array, *,
                        chunk: int) -> jax.Array:
    """SUM of `weights * CE(hidden @ head.T, targets)` computed in
    sequence chunks under `jax.checkpoint` — the shared fused-head CE
    core of `chunked_lm_loss` (causal shift + uniform weights) and
    `bert.chunked_mlm_loss` (masked-position weights): the [B, S, V]
    logits never materialize, each chunk's are recomputed in the
    backward. Padding rows carry weight 0, so ragged S is exact."""
    B, S, D = hidden.shape
    nc = -(-S // chunk)
    pad = nc * chunk - S
    h = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
    y = jnp.pad(targets, ((0, 0), (0, pad)))
    wts = jnp.pad(weights, ((0, 0), (0, pad)))
    h = h.reshape(B, nc, chunk, D).transpose(1, 0, 2, 3)
    y = y.reshape(B, nc, chunk).transpose(1, 0, 2)
    wts = wts.reshape(B, nc, chunk).transpose(1, 0, 2)
    w = head.astype(hidden.dtype)

    @jax.checkpoint
    def tick(total, xs):
        hc, yc, mc = xs
        logits = jnp.einsum("bcd,vd->bcv", hc, w).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, yc)
        return total + (ce * mc).sum(), None

    total, _ = lax.scan(tick, jnp.float32(0.0), (h, y, wts))
    return total


def make_lm_train_step(model: TransformerLM,
                       tx: optax.GradientTransformation, mesh,
                       *, moe_aux_weight: float = 0.01,
                       donate: bool = True,
                       loss_chunk: Optional[int] = None,
                       param_pspecs: Any = None) -> Callable:
    """step(params, opt_state, tokens) -> (params, opt_state, loss).

    `params` = unboxed pytree placed by `init_lm_state` (TP/EP leaves
    sharded per their `nn.Partitioned` annotations, the rest replicated);
    `tokens` [B, S] sharded (data, seq). One jit over the whole mesh: the
    gradient all-reduce over ``data`` (the reference's entire hot path,
    SURVEY §3.2) is inserted by GSPMD because params carry no ``data``
    axis, and XLA's collective combiner provides the tensor-fusion
    batching the reference implements by hand (`docs/tensor-fusion.md`).

    ``param_pspecs``: optional PartitionSpec pytree (e.g. from
    `lm_fsdp_specs`) pinning the UPDATED params — with FSDP this keeps
    the new params born ``data``-sharded so donation reuses the sharded
    buffers and GSPMD lowers the gradient sync as reduce-scatter, not
    all-reduce-then-slice.
    """
    has_moe = model.moe_every > 0

    def data_loss(params, tokens, mutable):
        return _lm_data_loss(model, params, tokens, loss_chunk,
                             mutable)

    def loss_fn(params, tokens):
        if has_moe:
            loss, col = data_loss(params, tokens, ["losses"])
            aux = sum(jnp.asarray(v).sum()
                      for v in jax.tree.leaves(col.get("losses", {})))
            return loss + moe_aux_weight * aux
        loss, _ = data_loss(params, tokens, False)
        return loss

    if param_pspecs is not None:
        from horovod_tpu.parallel.fsdp import constrain_tree

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        if param_pspecs is not None:
            grads = constrain_tree(grads, param_pspecs)
        updates, new_opt = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        if param_pspecs is not None:
            new_params = constrain_tree(new_params, param_pspecs)
        return new_params, new_opt, loss

    jitted = jax.jit(step, donate_argnums=(0, 1) if donate else ())

    def wrapped(params, opt_state, tokens):
        with use(mesh):
            return jitted(params, opt_state, tokens)

    from horovod_tpu.utils.timeline import step_bracket
    stepped = step_bracket(wrapped)
    # Same convention as models/train.py: `__wrapped__` resolves to
    # the innermost JITTED step (`step.__wrapped__.lower(...)`, under
    # `use(mesh)`, is how tests and chip_smoke.py read the program).
    stepped.__wrapped__ = jitted
    return stepped


def _lm_data_loss(model, params, tokens, loss_chunk, mutable):
    """Chunked-vs-plain loss dispatch shared by the train and eval
    steps (one site, so the eval==train-loss invariant can't drift)."""
    if loss_chunk:
        out = model.apply({"params": params}, tokens,
                          return_hidden=True, mutable=mutable)
        (hidden, embed), col = out if mutable else (out, {})
        return chunked_lm_loss(hidden, embed, tokens,
                               chunk=loss_chunk), col
    out = model.apply({"params": params}, tokens, mutable=mutable)
    logits, col = out if mutable else (out, {})
    return lm_loss(logits, tokens), col


def make_lm_eval_step(model: TransformerLM, mesh, *,
                      loss_chunk: Optional[int] = None) -> Callable:
    """eval(params, tokens) -> mean next-token cross entropy (nats).

    The forward-only twin of `make_lm_train_step` — same sharding, no
    gradient/optimizer; perplexity = exp(loss). Use `loss_chunk` to
    keep the [B, S, V] logits from materializing on long sequences
    (same trade as the train step's option).

    For MoE models this is PURE cross entropy: the train step's
    load-balancing aux term (`moe_aux_weight · aux`) is a training
    regularizer, not part of the modeled likelihood, so it is excluded
    here — the right number for perplexity, but expect the train
    step's reported loss to sit `moe_aux_weight · aux` above eval on
    the same batch.
    """
    def ev(params, tokens):
        return _lm_data_loss(model, params, tokens, loss_chunk,
                             False)[0]

    jitted = jax.jit(ev)

    def wrapped(params, tokens):
        with use(mesh):
            return jitted(params, tokens)

    return wrapped


def init_lm_state(model: TransformerLM, tx: optax.GradientTransformation,
                  rng, mesh, sample_tokens, *,
                  sharded_init: bool = False,
                  param_pspecs: Any = None) -> Tuple[Any, Any]:
    """Initialize and mesh-place (params, opt_state).

    Default path: params are initialized on the default device
    (`model.init`), unboxed, and placed per their partition annotations
    (`shard_params`); optimizer slots are pinned to their param's
    placement (`init_opt_state_sharded` — a bare `jit(tx.init)` would
    materialize them replicated).

    ``sharded_init=True``: sharded-at-birth — the init computation
    itself is jitted with `out_shardings` from the partition
    annotations, so every device materializes only its own shard and
    no single device ever holds the full parameter tree. Required once
    the model outgrows one device's HBM (TP/EP models at scale); same
    values as the default path (same keys, same program, partitioned
    by GSPMD).

    ``param_pspecs``: explicit PartitionSpec pytree overriding the
    annotation-derived specs — THE handle for FSDP/ZeRO. Compute it
    once with `lm_fsdp_specs(...)` and pass the same tree here and to
    `make_lm_train_step(param_pspecs=)`; one source of truth means the
    born sharding and the per-step pinning can't drift apart. Implies
    sharded-at-birth.
    """
    from horovod_tpu.parallel.fsdp import init_opt_state_sharded
    if not sharded_init and param_pspecs is None:
        variables = model.init(rng, sample_tokens)
        with use(mesh):
            params = shard_params(mesh, variables["params"])
            opt_state = init_opt_state_sharded(tx, params)
        return params, opt_state

    from jax.sharding import NamedSharding
    toks = jnp.asarray(sample_tokens)
    if param_pspecs is not None:
        specs = param_pspecs
    else:
        shapes = jax.eval_shape(model.init, rng, toks)
        specs = param_specs(shapes["params"])
    out_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))

    def init_fn(r):
        return unbox(model.init(r, toks)["params"])

    with use(mesh):
        # hvd: disable=HVD003(one-shot sharded param init at setup; out_shardings depends on the call's mesh)
        params = jax.jit(init_fn,
                         out_shardings=out_shardings)(rng)
        opt_state = init_opt_state_sharded(tx, params)
    return params, opt_state


def lm_fsdp_specs(model: TransformerLM, rng, sample_tokens, mesh, *,
                  fsdp_min_elems: Optional[int] = None):
    """The FSDP-overlaid PartitionSpec pytree for the model's params.

    The single source of truth for a ZeRO run — pass the SAME tree to
    `init_lm_state(param_pspecs=...)` and
    `make_lm_train_step(param_pspecs=...)`."""
    from horovod_tpu.parallel.fsdp import (
        DEFAULT_MIN_ELEMS, fsdp_param_specs)
    shapes = jax.eval_shape(model.init, rng,
                            jnp.asarray(sample_tokens))
    return fsdp_param_specs(
        param_specs(shapes["params"]), unbox(shapes["params"]), mesh,
        min_elems=(DEFAULT_MIN_ELEMS if fsdp_min_elems is None
                   else fsdp_min_elems))


def generate(model: TransformerLM, params, prompt, steps: int, *,
             mesh=None, temperature: float = 0.0, rng=None,
             top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             eos_id: Optional[int] = None,
             pad_id: int = 0,
             early_stop: bool = False) -> jax.Array:
    """Autoregressive generation with a KV cache.

    The reference's inference story is a docs recipe for stripping
    Horovod ops out of a frozen graph (`docs/inference.md` there); this
    is the TPU-native inference path in full: a decode-mode clone of the
    trained model (`decode=True` — K/V cached per block, one
    `dynamic_update_slice` per tick), driven by one `lax.scan` over
    prompt + generated positions inside a single jit, TP-composable
    (pass ``mesh``; the cache keeps heads on ``model``).

    `prompt` [B, P] int tokens; returns [B, P + steps]. Greedy at
    ``temperature=0``; otherwise softmax sampling with ``rng``,
    optionally truncated to the ``top_k`` highest-probability tokens
    and/or the ``top_p`` nucleus (smallest set with cumulative
    probability >= top_p).

    ``eos_id``: per-sequence stop token — once a sequence emits it,
    every later position is ``pad_id`` (the output stays a fixed
    [B, P + steps] rectangle; finished sequences simply stop changing,
    the standard batched-serving contract). By default the cache still
    advances for finished rows (same compiled program either way), so
    eos alone is a semantic knob, not a compute saver.

    ``early_stop`` (requires ``eos_id``): make it a compute saver —
    the decode loop runs as a `lax.while_loop` that exits as soon as
    EVERY row has emitted eos, instead of a fixed-length scan. The
    output keeps the same [B, P + steps] rectangle and the same
    post-eos padding contract (unvisited positions are ``pad_id``), so
    tokens are identical to the scan path; only the wall clock
    shrinks. The win compounds under `generate_bucketed`, where each
    bucket stops at its own last finisher.
    The prompt is prefilled in ONE forward pass (the decode-mode
    attention masks S>1 blocks causally against the cached prefix), so
    only the generated tokens pay the per-tick latency.
    """
    prompt = jnp.asarray(prompt)
    B, P = prompt.shape
    if steps <= 0:
        return prompt
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) requires rng")
    if (top_k is not None or top_p is not None) and temperature <= 0:
        raise ValueError("top_k/top_p require temperature > 0")
    if top_p is not None and not 0 < top_p <= 1:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k is not None and not 1 <= top_k <= model.vocab_size:
        raise ValueError(
            f"top_k must be in [1, vocab_size={model.vocab_size}], "
            f"got {top_k}")
    if eos_id is not None and not 0 <= eos_id < model.vocab_size:
        raise ValueError(
            f"eos_id must be in [0, vocab_size={model.vocab_size}), "
            f"got {eos_id}")
    if eos_id is not None and not 0 <= pad_id < model.vocab_size:
        # Pad tokens are fed back as inputs for finished rows; an
        # out-of-vocab id would gather-clamp silently.
        raise ValueError(
            f"pad_id must be in [0, vocab_size={model.vocab_size}), "
            f"got {pad_id}")
    if early_stop and eos_id is None:
        raise ValueError("early_stop requires eos_id (without a stop "
                         "token there is nothing to stop early on)")
    unbounded = model.context_unbounded
    if not unbounded and P + steps - 1 > model.max_len:
        # dynamic_update_slice would clamp writes past the cache end —
        # plausible-looking garbage, so refuse loudly instead. With
        # RoPE + a sliding window the cache is a rolling buffer and
        # positions are unbounded, so any length generates.
        raise ValueError(
            f"prompt ({P}) + steps ({steps}) - 1 exceeds "
            f"max_len={model.max_len} (use pos_emb='rope' with "
            f"window= for unbounded generation)")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    dec_model = model.clone(decode=True)
    # The cache is deterministically zeros; eval_shape gives its
    # structure without running a full-length forward or materializing
    # a second copy of the params.
    shapes = jax.eval_shape(
        dec_model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((B, model.max_len), prompt.dtype))
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         shapes["cache"])

    args = (dec_model, params, cache, prompt, rng, steps,
            float(temperature), top_k,
            None if top_p is None else float(top_p),
            None if eos_id is None else jnp.asarray(eos_id,
                                                    prompt.dtype),
            jnp.asarray(pad_id, prompt.dtype))
    if mesh is not None:
        with use(mesh):
            gen = _generate_scan(*args, greedy=temperature <= 0,
                                 early_stop=early_stop)
    else:
        gen = _generate_scan(*args, greedy=temperature <= 0,
                             early_stop=early_stop)
    return jnp.concatenate([prompt, gen], axis=1)


@functools.partial(jax.jit,
                   static_argnames=("dec_model", "steps", "greedy",
                                    "top_k", "early_stop"))
def _generate_scan(dec_model, params, cache, prompt, rng, steps,
                   temperature, top_k=None, top_p=None, eos=None,
                   pad=None, *, greedy=False, early_stop=False):
    """The compiled prefill+decode loop — module-level so the jit cache
    persists across `generate` calls (flax Modules hash by their
    dataclass fields, so same model config ⇒ cache hit).

    ``temperature``, ``top_p``, ``eos``, and ``pad`` are traced
    operands, so changing their values reuses the compiled program;
    what recompiles is the static ``greedy`` flag (temperature <= 0 —
    selects the argmax branch), ``top_k`` (a shape operand of
    `lax.top_k`), and toggling ``top_p`` or ``eos`` between None and
    a value (the arg pytree changes)."""

    def last_logits(cache, toks):
        """Apply one decode call and project ONLY the last position
        through the LM head — prefill never materializes the
        [B, P, vocab] logits tensor (the LM's biggest activation, the
        same one chunked_lm_loss exists to avoid)."""
        (hidden, embed), mut = dec_model.apply(
            {"params": params, "cache": cache}, toks,
            return_hidden=True, mutable=["cache"])
        logits = jnp.einsum("bd,vd->bv", hidden[:, -1],
                            embed.astype(hidden.dtype))
        return logits.astype(jnp.float32), mut["cache"]

    def pick(logits, r):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(prompt.dtype)
        logits = logits / temperature
        neg = jnp.finfo(logits.dtype).min
        if top_k is not None:
            kth = lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, neg, logits)
        if top_p is not None:
            logits = nucleus_mask(logits, top_p)
        nxt = jax.random.categorical(r, logits)
        return nxt.astype(prompt.dtype)

    # Prefill: the whole prompt in one forward (fills every block's
    # cache, yields the first generated token).
    rng, r0 = jax.random.split(rng)
    logits, cache = last_logits(cache, prompt)
    tok0 = pick(logits, r0)
    # Per-sequence stop: the eos token itself is emitted, every later
    # position is pad (fixed-rectangle output; the cache still ticks
    # for finished rows — one compiled program either way).
    done0 = (tok0 == eos if eos is not None
             else jnp.zeros(tok0.shape, bool))

    def tick(carry, _):
        cache, tok, r, done = carry
        r, r_tick = jax.random.split(r)
        logits, cache = last_logits(cache, tok[:, None])
        nxt = pick(logits, r_tick)
        if eos is not None:
            nxt = jnp.where(done, pad, nxt)
            done = done | (nxt == eos)
        return (cache, nxt, r, done), nxt

    if early_stop:
        # while_loop twin of the scan below: same tick body writing
        # into a pad-prefilled [B, steps-1] buffer, but the loop exits
        # as soon as every row is done — unvisited columns stay pad,
        # so the output rectangle is identical to the scan path's.
        B = prompt.shape[0]
        buf0 = jnp.full((B, steps - 1), pad, prompt.dtype)

        def cond(state):
            t, carry, _ = state
            done = carry[3]
            return (t < steps - 1) & ~done.all()

        def body(state):
            t, carry, buf = state
            carry, nxt = tick(carry, None)
            buf = lax.dynamic_update_slice(
                buf, nxt[:, None], (jnp.zeros((), t.dtype), t))
            return t + 1, carry, buf

        _, _, outs = lax.while_loop(
            cond, body, (jnp.zeros((), jnp.int32),
                         (cache, tok0, rng, done0), buf0))
        return jnp.concatenate([tok0[:, None], outs], axis=1)

    (_, _, _, _), outs = lax.scan(
        tick, (cache, tok0, rng, done0), None, length=steps - 1)
    return jnp.concatenate([tok0[:, None], outs.T], axis=1)  # [B, steps]


def generate_bucketed(model: TransformerLM, params, prompts,
                      steps: int, **kw):
    """Mixed-length batched serving via length bucketing.

    `generate` shares one prompt length P per call (the KV cache keeps
    a single scalar fill index — docs/inference.md's batched-serving
    contract). This helper makes the documented workaround an API:
    ``prompts`` is a LIST of 1-D int token arrays; same-length prompts
    are grouped into one shared-P `generate` call each, and results
    come back in input order as a list of 1-D [P_i + steps] arrays.
    All `generate` kwargs pass through — eos_id/pad_id keep the same
    post-eos padding contract per row, and ``early_stop=True`` (with
    eos_id) stops each bucket's decode loop at that bucket's last
    finisher instead of always paying all ``steps`` ticks. One
    compile per distinct (length, batch-size) pair — the standard
    serving-bucket trade.
    """
    arrs = [jnp.asarray(p) for p in prompts]
    by_len: dict = {}
    for idx, p in enumerate(arrs):
        if p.ndim != 1:
            raise ValueError(
                f"generate_bucketed wants 1-D prompts, got shape "
                f"{p.shape}; for an already-rectangular batch call "
                f"generate directly")
        by_len.setdefault(p.shape[0], []).append(idx)
    out: list = [None] * len(arrs)
    for n, idxs in by_len.items():
        bkw = kw
        if kw.get("rng") is not None:
            # Independent sample streams per bucket: the same key fed
            # to every call would replay identical Gumbel noise.
            bkw = dict(kw, rng=jax.random.fold_in(kw["rng"], n))
        res = generate(model, params,
                       jnp.stack([arrs[i] for i in idxs]), steps,
                       **bkw)
        for row, i in enumerate(idxs):
            out[i] = res[row]
    return out


# ---------------------------------------------------------------------------
# Slot-aware decode (the device surface of `horovod_tpu.serving`).
#
# `generate` shares ONE scalar `cache_index` across the batch, so every
# row must be at the same fill level — fine for offline batches, fatal
# for continuous batching, where each slot of the decode batch holds a
# different request at a different depth. These primitives generalize
# the linear cache to a SLOT POOL: every cache leaf gains a leading
# [num_slots] axis (so the per-layer `cache_index`/`pos_index` scalars
# become per-slot vectors), prefill appends into one slot's rows via
# the `chunked_prefill` cache-wide-mask path (correct at any fill), and
# the decode tick `jax.vmap`s the B=1 decode step over the slot axis —
# per-slot RoPE offsets, per-slot prefix-attention trip counts, and the
# per-row `dynamic_update_slice` cache writes all fall out of the vmap.
# ---------------------------------------------------------------------------

def slot_decode_model(model: TransformerLM) -> TransformerLM:
    """The decode-mode clone every slot primitive shares. ONE clone
    config (decode + chunked_prefill) serves both prefill chunks (S>1
    appends at arbitrary fill) and S=1 ticks, so the flax-module hash —
    and therefore the jit cache — is shared across all of them."""
    return model.clone(decode=True, chunked_prefill=True)


def decode_attention_plan(model: TransformerLM, lanes: int = 1,
                          kind: Optional[str] = None):
    """The way an S = 1 step of ``model``'s softmax layers of ``kind``
    (None: the first kind the model has) attends against their cache,
    as `ParallelSelfAttention` decides it when a tick is traced under
    the ambient mesh: the `ops.flash_attention.DecodePlan` (ragged
    kernel or lax, and why) for ``lanes`` slots - over the linear
    cache of ``max_len``, or over the ring of a kind with a window.
    `kernel_plans` answers every kind."""
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel.tensor import _mesh_is_trivial
    if kind == "mla" or (kind is None and not model.softmax_kinds
                         and model.has_latent_cache):
        # `LatentAttention._kernel_plan`'s question, asked from outside
        return latent_decode_plan(
            lanes, model.max_len, model.num_heads, model.latent,
            itemsize=jnp.dtype(model.dtype or jnp.float32).itemsize,
            impl=model.decode_prefix_impl)
    if not model.softmax_kinds:
        return flash_attention.DecodePlan("lax", "no softmax layer")
    spec = model.attn_spec(kind or model.softmax_kinds[0])
    ring = spec.window is not None
    W, blk = (spec.window if ring else model.max_len,
              model.decode_prefix_block)
    if not ring and (not blk or W % min(blk, W)):
        return flash_attention.DecodePlan(
            "lax", "decode_prefix_block off: the cache-wide mask")
    return flash_attention.decode_attention_plan(
        lanes, W, spec.num_heads,
        model.num_kv_heads or spec.num_heads, model.head_dim,
        itemsize=jnp.dtype(model.dtype or jnp.float32).itemsize,
        impl=model.decode_prefix_impl,
        quantized=model.kv_quant is not None,
        trivial_mesh=_mesh_is_trivial(), ring=ring)


def kernel_plans(model: TransformerLM, lanes: int = 1,
                 chunk: int = 1) -> dict:
    """Which program steps each kind of ``model``'s layers over
    ``lanes`` slots and why, as the layers decide it under the ambient
    mesh when a tick or a prompt chunk of ``chunk`` tokens is traced.
    Every family is a dict of plan records (``path``, ``why``,
    ``describe()``) in the model's order:

    * ``"decode_attn"``: {kind: `decode_attention_plan`} over the
      softmax kinds and the latent kind (one entry, the plan that says
      so, for a model without any);
    * ``"moe_product"``: {"tick" | "prefill": the
      `ops.grouped_matmul.GroupedPlan` the dropless expert layers
      multiply their (token, expert) pairs with}; {} without such a
      layer;
    * ``"state_step"``: {kind ("kda", "ssm"): the
      `ops.kda_step.StateStepPlan` of a recurrent layer's S = 1 step,
      which the slot tick's freeze obeys}; {} without such a layer.

    The pools enter their mesh and call this, the engine logs it at
    warm-up and `metrics_snapshot()` carries it, so a run that fell
    back to a lax path says so."""
    from horovod_tpu.parallel import linear_attention, state_space
    from horovod_tpu.parallel.expert import product_plan
    kinds = model.softmax_kinds + (
        ("mla",) if model.has_latent_cache else ())
    products = {}
    if model.moe_every > 0 and model.moe_impl == "dropless":
        d = model.hidden_size or model.num_heads * model.head_dim
        held = (model.moe_held or (0, model.num_experts))[1]
        w_gate = jax.ShapeDtypeStruct(
            (held, d, model.moe_hidden or model.mlp_ratio * d),
            jnp.dtype(model.dtype or jnp.float32))
        routed = model.num_experts + model.moe_zero_experts
        products = {
            name: product_plan(tokens, model.moe_k, routed, w_gate)
            for name, tokens in (("tick", lanes), ("prefill", chunk))}
    steps = {
        "kda": lambda: linear_attention.state_step_plan(
            lanes, model.num_heads, model.head_dim),
        "ssm": lambda: state_space.state_step_plan(lanes, model.ssm)}
    return {
        "decode_attn": {kind: decode_attention_plan(model, lanes, kind)
                        for kind in kinds or ("attn",)},
        "moe_product": products,
        "state_step": {kind: steps[kind]()
                       for kind in model.recurrent_kinds}}


def init_slot_cache(model: TransformerLM, num_slots: int):
    """Zero-filled slot-pool cache: each leaf of the B=1 decode cache
    with a leading [num_slots] axis (K/V [num_slots, 1, max_len, Hkv,
    D] - `ops.flash_attention.kv_pack` heads to a row where a head is
    narrower than 128 lanes: [.., Hkv // pack, D * pack]; the scalar
    fill indices become [num_slots] vectors)."""
    dec_model = slot_decode_model(model)
    shapes = jax.eval_shape(
        dec_model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, model.max_len), jnp.int32))
    return jax.tree.map(
        lambda s: jnp.zeros((num_slots,) + s.shape, s.dtype),
        shapes["cache"])


def _serve_kv_axis(axis: Optional[str]) -> str:
    """The mesh axis serving KV shards ride (``HVD_SERVE_MESH_AXIS``,
    default the tensor-parallel ``model`` axis — KV heads live with
    their query groups' attention shards)."""
    if axis is not None:
        return axis
    from horovod_tpu.runtime.config import config as _cfg
    return _cfg.serve_mesh_axis or AXIS_MODEL


def shard_slot_cache(cache, mesh, axis: Optional[str] = None):
    """Commit a slot-pool cache (`init_slot_cache` layout) onto
    ``mesh``: KV leaves shard along the HEADS axis — dim 3 of
    [num_slots, 1, max_len, Hkv, ...] (K/V values and their int8-KV
    scale twins both carry Hkv there) — over the serving mesh axis;
    the per-slot fill-index vectors replicate (host-replicated int32
    metadata, one host decision drives all shards). GQA-aware via
    `safe_spec`: a heads count the axis size doesn't divide keeps the
    leaf replicated — KV heads partition with their query groups only
    when they can, never unevenly."""
    from jax.tree_util import tree_flatten_with_path, tree_unflatten
    from horovod_tpu.parallel.mesh import _place, safe_spec, sharding
    axis = _serve_kv_axis(axis)
    flat, treedef = tree_flatten_with_path(cache)
    out = []
    for path, leaf in flat:
        spec = (P() if "index" in str(path) else
                safe_spec(mesh, P(None, None, None, axis), leaf.shape))
        out.append(_place(leaf, sharding(mesh, *spec)))
    return tree_unflatten(treedef, out)


def shard_paged_pools(pools, mesh, axis: Optional[str] = None):
    """Commit paged block pools (`init_paged_pools` layout) onto
    ``mesh``: every pool leaf is [num_blocks, 1, block_size, Hkv, ...]
    — the heads axis sits at dim 3 exactly as in the linear slot
    cache — so each device holds its head slice of EVERY block, and a
    host-side block id names a mesh-wide block SHARD set. Same
    GQA-aware degrade as `shard_slot_cache`."""
    from horovod_tpu.parallel.mesh import _place, safe_spec, sharding
    axis = _serve_kv_axis(axis)
    return [
        _place(p, sharding(mesh, *safe_spec(
            mesh, P(None, None, None, axis), p.shape)))
        for p in pools]


def gather_block_rows(pools, block_ids):
    """Pull the [len(block_ids), 1, block_size, ...] rows of every
    pool leaf for a block-id list (KV-block export: the per-leaf
    device buffers a prefill pool hands to a decode pool). Plain
    fancy-index gather — stays on device; callers decide whether to
    bounce through the host (`np.asarray`) or `device_put` straight
    into the destination layout."""
    idx = jnp.asarray(block_ids, jnp.int32)
    return [p[idx] for p in pools]


@functools.partial(jax.jit, static_argnames=("dec_model",),
                   donate_argnums=(1,))
def slot_reset(dec_model, cache, slot):
    """Zero one slot's rows across every cache leaf (alloc/retire
    hygiene: fill indices return to 0; stale K/V past the new fill is
    never attended — the causal masks see positions, not bytes — but
    zeroing the whole row keeps the slot's state trivially inspectable
    and stops idle-slot index creep from inflating the shared vmapped
    tick's prefix-attention trip count)."""
    del dec_model  # part of the key so all slot fns share a cache line
    return jax.tree.map(
        lambda l: l.at[slot].set(jnp.zeros(l.shape[1:], l.dtype)),
        cache)


# What a row of `_moe_pairs` holds after the held experts' pairs where
# the model has identity experts (`HeldExpertsMoE.zero_experts`).
MOE_ROUTED_COLUMNS = ("moe_zero_pairs", "moe_chosen_pairs")
# ... and where its choice is limited to groups over a share of the
# experts (`HeldExpertsMoE.groups` with ``held``): the chips of the
# stated deployment that the tokens' experts lie on.
MOE_CHIPS_COLUMNS = ("moe_token_chips",)


def moe_stat_columns(model: "TransformerLM") -> Tuple[str, ...]:
    """Names of the counts that a row of `_moe_pairs` holds after the
    held experts' pairs, in the row's order; () for most models."""
    return ((MOE_ROUTED_COLUMNS if model.moe_zero_experts else ())
            + (MOE_CHIPS_COLUMNS if model.moe_groups is not None
               and model.moe_held is not None else ()))


def _moe_pairs(dec_model, mut):
    """What the dropless expert layers sowed in one apply, in layer
    order: int32 [expert layers, experts held], the (token, expert)
    pairs on each held expert; [0, 0] for a model without such a
    layer. A model with identity experts (``moe_zero_experts``) gets
    `MOE_ROUTED_COLUMNS` as two more columns: the pairs on identity
    experts and all the pairs chosen; one whose choice is limited to
    groups over a share gets `MOE_CHIPS_COLUMNS` (`moe_stat_columns`
    names what a row holds)."""
    sown = mut.get("moe_stats", {})
    layers = [sown[f"block_{i}"]["moe"]
              for i in range(dec_model.num_layers) if f"block_{i}" in sown]
    # a layer with identity experts adds its two `routed` counts
    # (`MOE_ROUTED_COLUMNS`), one with a group rule over a share its
    # `token_chips`, as the row's last columns. `moe_stat_columns` alone
    # decides which - the host strips the columns by the same function -
    # so a layer that did not sow what it names fails here, at trace
    # time, and not as a count read for an expert's pairs.
    columns = moe_stat_columns(dec_model)

    def row(m):
        parts = [m["pairs"]]
        if MOE_ROUTED_COLUMNS[0] in columns:
            parts.append(m["routed"])
        if MOE_CHIPS_COLUMNS[0] in columns:
            parts.append(m["token_chips"][None])
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    rows = [row(m) for m in layers]
    return jnp.stack(rows) if rows else jnp.zeros((0, 0), jnp.int32)


@hot_path
@functools.partial(jax.jit, static_argnames=("dec_model",),
                   donate_argnums=(2,))
def slot_prefill_chunk(dec_model, params, cache, slot, chunk,
                       count=None):
    """Append one [C]-token prompt chunk into slot ``slot``'s cache and
    return ``(cache, last-position logits [V], expert pairs)`` - the
    last as `_moe_pairs` has them.

    Runs the `chunked_prefill` path (cache-wide mask — correct for ANY
    current fill), and ``slot`` is a traced operand, so the same
    program serves every slot and every fill. A prompt of any length P
    streams in as the chunks of `prefill_chunks`, and under a chunk
    width C that is TWO compiled programs, whatever P:

    * the whole chunk (``count`` None): all C positions are the
      prompt's - no count, no mask;
    * the tail chunk (``count`` a traced int32 in 1 .. C): the first
      ``count`` positions are the prompt's, the rest are pad, which
      leaves every cache as it found it and is routed to no expert
      (`TransformerLM.__call__` has the rule by cache kind). The
      logits are read at position ``count - 1``; one program serves
      every tail length.

    Without a width the chunks are a binary decomposition: at most
    log2(max_len) programs, each compiled on first use."""
    sub = jax.tree.map(lambda l: l[slot], cache)
    (hidden, embed), mut = dec_model.apply(
        {"params": params, "cache": sub}, chunk[None, :],
        return_hidden=True, count=count,
        mutable=["cache", "moe_stats"])
    last = (hidden[0, -1] if count is None else
            lax.dynamic_index_in_dim(hidden[0], count - 1, 0,
                                     keepdims=False))
    logits = jnp.einsum("d,vd->v", last, embed.astype(hidden.dtype))
    cache = jax.tree.map(lambda l, s: l.at[slot].set(s), cache,
                         mut["cache"])
    return cache, logits.astype(jnp.float32), _moe_pairs(dec_model, mut)


def chunk_width(max_chunk: Optional[int],
                max_len: Optional[int] = None) -> Optional[int]:
    """The positions of a chunk program under a budget of ``max_chunk``
    prompt tokens a scheduler step (the Sarathi-style knob behind
    HVD_PREFILL_CHUNK_BUDGET): the largest power of two <= max_chunk
    (and <= ``max_len``, the rows a cache has to take a chunk into).
    None without a budget: there is no width to pad a tail to."""
    if max_chunk is None or max_chunk < 1:
        return None
    cap = int(max_chunk if max_len is None else min(max_chunk, max_len))
    return 1 << (max(1, cap).bit_length() - 1)


def prefill_chunks(length: int, max_chunk: Optional[int] = None, *,
                   pad_tail: bool = True) -> list:
    """The REAL tokens of each chunk a prompt of ``length`` tokens is
    streamed in - the schedule `slot_prefill_chunk` is fed with.

    Under a budget, C = `chunk_width` (max_chunk): ``length // C``
    whole chunks and, where ``length % C`` is not 0, ONE tail chunk of
    that many tokens, which the pool pads to C positions and runs with
    its true count a traced operand (200 at 64 -> [64, 64, 64, 8];
    3 at 8 -> [3]): two programs, whatever the length. The scheduler
    interleaves one bounded chunk with decode ticks instead of
    streaming a whole long prompt back-to-back.

    Without one (``max_chunk`` None: the whole prompt back to back),
    or with ``pad_tail`` False (a pool whose chunk program takes no
    count: the paged pool), the remainder is its binary decomposition
    into descending powers of two (13 -> [8, 4, 1]; 200 at 64 ->
    [64, 64, 64, 8]; 3 at 8 -> [2, 1]): at most log2 programs, and a
    chunk's width is its length."""
    if length <= 0:
        raise ValueError(f"prompt length must be positive, got {length}")
    out = []
    cap = chunk_width(max_chunk)
    if cap is not None:
        out = [cap] * (length // cap)
        length -= cap * (length // cap)
        if pad_tail:
            return out + [length] * (length > 0)
    return out + [1 << b for b in range(length.bit_length() - 1, -1, -1)
                  if length >> b & 1]


def nucleus_mask(logits, top_p):
    """Top-p (nucleus) truncation: mask (to -max) every logit outside
    the smallest prefix of the sorted distribution with cumulative
    probability >= top_p; the first token is always kept. THE one
    nucleus rule — `generate`'s pick and the serving tick's
    `sample_token` both call it, so the two paths cannot drift."""
    neg = jnp.finfo(logits.dtype).min
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    keep = csum - probs < top_p
    # Threshold = smallest kept logit; mask everything below.
    thresh = jnp.min(jnp.where(keep, sorted_logits, jnp.inf),
                     axis=-1, keepdims=True)
    return jnp.where(logits < thresh, neg, logits)


def sample_token(logits, temperature, top_p, key):
    """One sampled (or greedy) token from [V] logits, with TRACED
    temperature/top_p: temperature <= 0 selects argmax, top_p >= 1
    disables the nucleus truncation (`nucleus_mask`, shared with
    `generate`'s pick). THE per-lane rule - and the whole of its work
    whatever the lane asks for: both `where`s select between values
    already computed, so the sort and the draw run for a greedy lane
    too. The decode programs therefore do not vmap this themselves;
    `sample_lanes` decides on the batch which part of it is needed
    and calls it only where some lane wants a nucleus."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    sampled = jax.random.categorical(
        key, jnp.where(top_p < 1.0, nucleus_mask(scaled, top_p),
                       scaled))
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _sample_lane_plain(logits, temperature, key):
    """`sample_token` for a lane that asks for no nucleus: the same
    argmax, the same divide, the same draw from the same key on the
    same operand (``scaled``) - without the sort that `sample_token`
    computes and then does not select."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    sampled = jax.random.categorical(key, scaled)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def sample_lanes(logits, temperature, top_p, keys):
    """One token a lane from [S, V] float32 logits, [S] temperatures,
    [S] top_ps and [S] keys: `sample_token` over the lanes, with the
    work decided ONCE, on the batch, by a scalar the device computes
    from the lanes' own parameters - so one compiled program still
    serves every request mix, and only the branch that some lane
    needs is executed (`lax.switch` on a scalar; under a vmap it
    would lower to a select and run all three):

    0. no lane has ``temperature > 0``: `argmax` alone - no divide,
       no noise, no sort;
    1. some lane samples, none of those with ``top_p < 1``: the
       categorical draw on the scaled logits, no sort;
    2. some sampling lane has ``top_p < 1``: `sample_token` for the
       whole batch, per-lane `where`s and all.

    A batch pays for its most demanding lane. Every lane's token is
    bitwise `sample_token`'s in every mix: a greedy lane is the argmax
    of the same logits on every path, and a sampling lane draws from
    the same key on the same operand. The predicates read every lane,
    live or not: the pools write a lane's parameters when its prefill
    closes and reset them to 0.0 / 1.0 when it is freed
    (`SlotPool.finish_prefill`, `free`), so a lane no request holds
    never asks for more than argmax."""
    sampling = temperature > 0.0
    path = (jnp.any(sampling).astype(jnp.int32)
            + jnp.any(sampling & (top_p < 1.0)).astype(jnp.int32))
    return jax.lax.switch(
        path,
        (lambda lg, t, p, k: jnp.argmax(lg, axis=-1),
         lambda lg, t, p, k: jax.vmap(_sample_lane_plain)(lg, t, k),
         jax.vmap(sample_token)),
        logits, temperature, top_p, keys)


def recurrent_leaf(path) -> bool:
    """A cache leaf that is a recurrent layer's state: under the
    scope of one of `RECURRENT_KINDS`, a variable that kind's layer
    itself lists as overwritten each step (`OVERWRITTEN`)."""
    return _leaf_of(path, "OVERWRITTEN")


def _leaf_of(path, listed: str, kinds=RECURRENT_KINDS) -> bool:
    """Is ``path`` a cache variable that the recurrent layer it lies
    under (a scope `<kind>` or `<kind>_<n>` of ``kinds``) names in its
    attribute ``listed``?"""
    keys = [getattr(p, "key", None) for p in path]
    if len(keys) < 2 or not isinstance(keys[-2], str):
        return False
    kind = keys[-2].split("_")[0]
    return kind in kinds and keys[-1] in getattr(
        RECURRENT_LAYERS[kind], listed)


def overwritten_leaf(path) -> bool:
    """A cache leaf that a step OVERWRITES: the fill indices and a
    recurrent layer's state. K/V rows are appended to instead."""
    return "index" in str(path) or recurrent_leaf(path)


def _freeze_cache_indices(new_cache, old_cache, advance, kept=()):
    """Select per-leaf between the advanced and the input cache (scalar
    ``advance`` under the tick's vmap): a lane that must not move
    (FREE or mid-prefill slots riding the shared vmapped tick,
    finished-but-unretired slots) keeps its old fill indices - and its
    old recurrent state and convolution tail, which a step overwrites:
    a tick interleaved with a chunked prefill would otherwise corrupt
    the half-built state of that slot. The K/V bytes the masked lane
    wrote at its frozen position are harmless - the causal masks attend
    positions < index, and the next real writer (prefill chunk or live
    tick) lands on the same position - so the [max_len] cache rows
    never need the select. Nor do a sliding-window layer's [window]
    rows: with its index frozen at i the ring stands still - the one
    slot the masked lane wrote, i mod window, held position
    i - window, which is outside the band of every position >= i, and
    the next real writer of position i lands on that slot.

    ``kept`` names the recurrent kinds whose step, told which lanes
    advance, kept `KEPT_BY_KERNEL` itself (where the state's step is
    the in-place kernel): those leaves pass as they are - one more
    reader of the old value, and XLA copies the leaf before the call
    that overwrites it."""
    from jax.tree_util import tree_flatten_with_path, tree_unflatten
    flat, treedef = tree_flatten_with_path(new_cache)
    old_leaves = jax.tree.leaves(old_cache)
    out = [jnp.where(advance, leaf, old)
           if overwritten_leaf(path)
           and not _leaf_of(path, "KEPT_BY_KERNEL", kept) else leaf
           for (path, leaf), old in zip(flat, old_leaves)]
    return tree_unflatten(treedef, out)


@hot_path
@functools.partial(jax.jit, static_argnames=("dec_model",),
                   donate_argnums=(2,))
def slot_decode_tick(dec_model, params, cache, toks, temps, top_ps,
                     rngs, live, done, eos):
    """One continuous-batching decode tick over EVERY slot: vmap of the
    B=1 decode step over the slot axis. Returns ``(cache, next_toks
    [num_slots], new_rngs, done, expert pairs)`` - the last as
    `_moe_pairs` has them, summed over the lanes that decode. (A
    dropless expert layer inside the vmap still sees every lane's
    token at once: `parallel.expert.grouped_experts` batches itself.)
    One compiled program serves every occupancy pattern; per-slot
    occupancy state is traced:

    * ``live`` [S] bool — host-known active lanes. Non-live lanes
      (FREE or mid-prefill slots) still ride the vmapped step but
      their cache fill indices, and a recurrent layer's state, are
      FROZEN (`_freeze_cache_indices`; where `kernel_plans` says
      "kernel" the layer is told which lanes advance and its in-place
      step keeps the state itself), so an idle lane never creeps
      its index — and with it the shared prefix-attention trip count
      every live slot pays for — and a partially prefilled slot's
      next chunk lands exactly where the previous one stopped.
    * ``done`` [S] bool + ``eos`` scalar (pass -1 to disable) — ON-
      DEVICE stop detection: a lane that has emitted eos keeps
      emitting eos (never a post-eos garbage token) and stops
      advancing its cache, all decided on device. The host can
      therefore retire from the (asynchronously transferred) token
      buffer alone, pipeline-depth ticks late, without a second
      device->host sync per tick to check stops.
    * ``temps`` / ``top_ps`` [S] — each lane's sampling parameters,
      traced too. The vmap ends at the lane's float32 logits and its
      split key (every lane splits every tick: a request's stream is
      keyed by token ordinal); `sample_lanes` then picks the tokens
      on the batch - argmax alone when no lane samples, a draw with
      no sort when none of the sampling lanes has ``top_p < 1``, the
      per-lane rule `sample_token` for every lane otherwise. A batch
      pays for its most demanding lane; a lane's token is the same
      on every path.
    """

    # the recurrent kinds whose step keeps its state itself for a lane
    # that does not advance (the in-place kernel): never selected after
    kept = tuple(kind for kind, plan in kernel_plans(
        dec_model, toks.shape[0])["state_step"].items()
        if plan.path == "kernel")

    def one(sub, tok, rng, lv, dn):
        told = {"advance": lv & ~dn} if kept else {}
        (hidden, embed), mut = dec_model.apply(
            {"params": params, "cache": sub}, tok[None, None],
            return_hidden=True, mutable=["cache", "moe_stats"], **told)
        new = _freeze_cache_indices(mut["cache"], sub, lv & ~dn, kept)
        logits = jnp.einsum("d,vd->v", hidden[0, -1],
                            embed.astype(hidden.dtype))
        rng, r = jax.random.split(rng)
        return (new, logits.astype(jnp.float32), rng, r,
                _moe_pairs(dec_model, mut))

    cache, logits, rngs, keys, pairs = jax.vmap(one)(
        cache, toks, rngs, live, done)
    nxt = sample_lanes(logits, temps, top_ps, keys).astype(toks.dtype)
    emit = jnp.where(done, eos.astype(toks.dtype), nxt)
    decoding = (live & ~done)[:, None, None]
    return cache, emit, rngs, done | (emit == eos), jnp.sum(
        jnp.where(decoding, pairs, 0), axis=0)


# ---------------------------------------------------------------------------
# Paged slot cache (the device surface of `horovod_tpu.serving.paging`).
#
# The slot-pool cache above still RESERVES a private [max_len] KV region
# per slot, so device KV capacity is num_slots x max_len regardless of
# how long requests actually run — the same per-tensor-allocation waste
# Horovod's fusion buffer removed for gradients, here applied to KV
# state. These primitives carve the cache into fixed-size BLOCKS
# instead (vLLM-style): one shared pool of [num_blocks, 1, block_size,
# ...] rows per cache leaf, and each sequence owns an int32 BLOCK TABLE
# mapping its logical positions to pool blocks. The table and the fill
# index are TRACED operands, so one compiled program serves every
# layout; the per-tick view of a sequence's KV is a gather of its
# blocks (`pool[table]`), reshaped back to the exact [1, max_len, ...]
# linear layout the decode attention already consumes — the compute is
# the SAME flax apply on the SAME values, which is what makes the paged
# path bitwise-equal to the slot pool (pinned by tests). Writes scatter
# only the newly produced rows back into their blocks; lanes that must
# not advance (FREE, mid-prefill, done) route their row to the reserved
# NULL block 0, whose content is never attended (every decode mask
# attends positions < fill only).
# ---------------------------------------------------------------------------

class PagedCacheSpec:
    """Static (hashable — rides jit static args) description of one
    paged slot cache: the B=1 decode-cache tree structure, each leaf's
    kind ("kv" = pooled into blocks, "index" = the per-lane fill
    scalar), the block geometry, and — for the paged-kernel mode —
    each leaf's tree path plus the KV leaves' tail shapes/dtypes (so
    the kernel path can build the per-call staging cache and the
    "paged" collection without a shapes re-eval inside jit). Built
    once per pool via `paged_cache_spec`."""

    __slots__ = ("treedef", "kinds", "block_size", "blocks_per_seq",
                 "paths", "kv_shapes", "kv_dtypes")

    def __init__(self, treedef, kinds, block_size, blocks_per_seq,
                 paths=(), kv_shapes=(), kv_dtypes=()):
        self.treedef = treedef
        self.kinds = tuple(kinds)
        self.block_size = int(block_size)
        self.blocks_per_seq = int(blocks_per_seq)
        self.paths = tuple(tuple(p) for p in paths)
        self.kv_shapes = tuple(tuple(s) for s in kv_shapes)
        self.kv_dtypes = tuple(str(d) for d in kv_dtypes)

    @property
    def view_len(self) -> int:
        return self.block_size * self.blocks_per_seq

    def _key(self):
        return (self.treedef, self.kinds, self.block_size,
                self.blocks_per_seq, self.paths, self.kv_shapes,
                self.kv_dtypes)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return (isinstance(other, PagedCacheSpec)
                and self._key() == other._key())


def paged_cache_spec(model: TransformerLM,
                     block_size: int) -> PagedCacheSpec:
    """Classify the B=1 decode cache's leaves for paging. KV-bearing
    leaves (``cached_key``/``cached_value`` and their int8-KV scale
    twins) carry the max_len axis at position 1 and are pooled into
    blocks; ``cache_index``/``pos_index`` scalars become the per-lane
    fill vector the paged pool keeps outside the tree. Requires
    ``block_size`` to divide ``max_len`` exactly, so the gathered view
    is shape-identical to the linear cache (the bitwise-equality
    contract), and no sliding window (a rolling buffer's slot = pos
    mod window layout has no block-aligned prefix to share)."""
    if model.has_rolling_cache:
        raise ValueError(
            "paged KV cache requires window=None on every layer (a "
            "rolling-window cache has no block-aligned prefix to page "
            "or share)")
    if model.has_latent_cache:
        raise ValueError(
            "paged KV cache has no block form of a latent-attention "
            "layer's rows (one head-less leaf a layer; the block pools "
            "and the paged kernels want a K and a V leaf with a head "
            "axis)")
    if block_size < 1 or model.max_len % block_size:
        raise ValueError(
            f"block_size must divide max_len={model.max_len} exactly, "
            f"got {block_size}")
    from jax.tree_util import tree_flatten_with_path
    dec_model = slot_decode_model(model)
    shapes = jax.eval_shape(
        dec_model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, model.max_len), jnp.int32))["cache"]
    flat, treedef = tree_flatten_with_path(shapes)
    kinds, paths, kv_shapes, kv_dtypes = [], [], [], []
    for path, leaf in flat:
        paths.append(tuple(getattr(p, "key", str(p)) for p in path))
        if "index" in str(path):
            assert leaf.shape == (), (path, leaf.shape)
            kinds.append("index")
        else:
            assert leaf.shape[:2] == (1, model.max_len), (path,
                                                          leaf.shape)
            kinds.append("kv")
            kv_shapes.append(leaf.shape[2:])
            kv_dtypes.append(leaf.dtype)
    return PagedCacheSpec(treedef, kinds, block_size,
                          model.max_len // block_size,
                          paths=paths, kv_shapes=kv_shapes,
                          kv_dtypes=kv_dtypes)


def init_paged_pools(model: TransformerLM, spec: PagedCacheSpec,
                     num_blocks: int) -> list:
    """Zero-filled block pools: one [num_blocks, 1, block_size, ...]
    array per KV leaf of the B=1 decode cache (flatten order). Block 0
    is the NULL block — never allocated to a sequence; masked lanes
    dump their dead writes there."""
    if num_blocks < 2:
        raise ValueError(
            f"num_blocks must be >= 2 (block 0 is the reserved null "
            f"block), got {num_blocks}")
    from jax.tree_util import tree_flatten_with_path
    dec_model = slot_decode_model(model)
    shapes = jax.eval_shape(
        dec_model.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, model.max_len), jnp.int32))["cache"]
    flat, _ = tree_flatten_with_path(shapes)
    pools = []
    for kind, (path, leaf) in zip(spec.kinds, flat):
        if kind == "kv":
            pools.append(jnp.zeros(
                (num_blocks, 1, spec.block_size) + leaf.shape[2:],
                leaf.dtype))
    return pools


def _paged_view(spec: PagedCacheSpec, pools, table, fill):
    """Assemble one lane's [1, max_len, ...] cache view from its block
    table: KV leaves are `pool[table]` gathers reshaped back to the
    linear layout; index leaves are the lane's fill scalar (every
    layer's cache_index — and pos_index at learned-position models —
    advances in lockstep, so ONE scalar determines them all). The
    table is a traced operand: one compiled program for all layouts."""
    leaves, pi = [], 0
    fill = jnp.asarray(fill, jnp.int32)
    for kind in spec.kinds:
        if kind == "kv":
            g = jnp.take(pools[pi], table, axis=0)   # [nb, 1, bs, ...]
            pi += 1
            g = jnp.moveaxis(g, 1, 0)                # [1, nb, bs, ...]
            leaves.append(g.reshape((1, spec.view_len) + g.shape[3:]))
        else:
            leaves.append(fill)
    from jax.tree_util import tree_unflatten
    return tree_unflatten(spec.treedef, leaves)


# Cache-leaf name -> the "paged" collection name its pool rides under
# (read by `ParallelSelfAttention._paged_attention`).
_POOL_NAMES = {"cached_key": "key_pool", "cached_value": "value_pool",
               "cached_key_scale": "key_scale_pool",
               "cached_value_scale": "value_scale_pool"}


def _paged_staging(spec: PagedCacheSpec, fill, length: int):
    """The paged-KERNEL mode's per-call "cache" collection: a tiny
    [1, length] staging buffer per KV leaf (the apply writes this
    call's new rows at position 0; the tick scatters them into their
    blocks afterwards) plus the index leaves — ``cache_index`` 0 (the
    staging write position) and ``pos_index`` the TRUE fill (learned
    positions slice their table at the absolute position). The real
    KV never materializes here: attention walks the pools through the
    "paged" collection (`_paged_collection`)."""
    from jax.tree_util import tree_unflatten
    leaves, ki = [], 0
    fill = jnp.asarray(fill, jnp.int32)
    for kind, path in zip(spec.kinds, spec.paths):
        if kind == "kv":
            leaves.append(jnp.zeros((1, length) + spec.kv_shapes[ki],
                                    spec.kv_dtypes[ki]))
            ki += 1
        else:
            leaves.append(fill if path[-1] == "pos_index"
                          else jnp.zeros((), jnp.int32))
    return tree_unflatten(spec.treedef, leaves)


def _paged_collection(spec: PagedCacheSpec, pools, table, fill):
    """The read-only "paged" variable collection for one lane's
    apply: each attention module's KV pools land at that module's
    path (key_pool/value_pool, plus the int8-KV scale pools when
    present), alongside the lane's block ``table`` and true ``fill``.
    Under the tick's vmap the pools are closed-over (UNBATCHED — one
    physical pool serves every lane) while table/fill are per-lane."""
    col, pi = {}, 0
    for kind, path in zip(spec.kinds, spec.paths):
        if kind != "kv":
            continue
        parent = col
        for seg in path[:-1]:
            parent = parent.setdefault(seg, {})
        parent[_POOL_NAMES[path[-1]]] = pools[pi]
        parent["table"] = table
        parent["fill"] = fill
        pi += 1
    return col


def _paged_cache_vars(spec: PagedCacheSpec, pools, params, table,
                      fill, length: int, fused: bool):
    """The apply's variable dict for one paged lane: the gathered
    [max_len] view (legacy/oracle path) or the staging + "paged"
    collection pair (kernel path) — THE single dispatch site the
    tick, the prefill chunk, and the speculative verify all share."""
    if fused:
        return {"params": params,
                "cache": _paged_staging(spec, fill, length),
                "paged": _paged_collection(spec, pools, table, fill)}
    return {"params": params,
            "cache": _paged_view(spec, pools, table, fill)}


def _paged_new_rows(spec: PagedCacheSpec, cache, fill, length: int):
    """The rows a decode/prefill apply just wrote into a view cache —
    positions [fill, fill+length) of every KV leaf, [length, ...] each
    (flatten order, matching the pools list)."""
    rows = []
    for kind, leaf in zip(spec.kinds, jax.tree.leaves(cache)):
        if kind == "kv":
            rows.append(lax.dynamic_slice_in_dim(
                leaf, fill, length, axis=1)[0])
    return rows


def _paged_scatter(spec: PagedCacheSpec, pools, rows, bids, offs):
    """Write freshly produced rows into their blocks: ``bids``/``offs``
    are parallel int32 vectors (block id, within-block offset) — one
    batched scatter per leaf. Duplicate (0, off) targets from masked
    lanes land in the null block, where last-writer-wins is harmless
    (null content is never attended)."""
    return [p.at[bids, 0, offs].set(r) for p, r in zip(pools, rows)]


@hot_path
@functools.partial(jax.jit,
                   static_argnames=("dec_model", "spec", "fused"),
                   donate_argnums=(2,))
def paged_prefill_chunk(dec_model, spec: PagedCacheSpec, pools, params,
                        tables, fills, slot, chunk, fused=False):
    """Append one [C]-token prompt chunk into lane ``slot``'s paged
    cache; returns ``(pools, fills, last-position logits [V])``. The
    lane's view is gathered through its block table (``fused=False``,
    the legacy/oracle path) or — the paged-kernel mode — the apply
    writes into a [1, C] staging buffer while attention walks only
    the filled blocks (`_paged_cache_vars`); either way the apply is
    the SAME `chunked_prefill` cache-wide-mask program the linear slot
    pool runs (correct at any fill — including a fill that starts past
    a shared-prefix span the admission matched and skipped), and only
    the chunk's C new rows scatter back into their blocks."""
    table = tables[slot]
    fill = fills[slot]
    C = chunk.shape[0]
    variables = _paged_cache_vars(spec, pools, params, table, fill,
                                  C, fused)
    (hidden, embed), mut = dec_model.apply(
        variables, chunk[None, :],
        return_hidden=True, mutable=["cache"])
    rows = _paged_new_rows(spec, mut["cache"],
                           jnp.int32(0) if fused else fill, C)
    pos = fill + jnp.arange(C, dtype=jnp.int32)
    bids = table[pos // spec.block_size]
    offs = pos % spec.block_size
    pools = _paged_scatter(spec, pools, rows, bids, offs)
    fills = fills.at[slot].set(fill + C)
    logits = jnp.einsum("d,vd->v", hidden[0, -1],
                        embed.astype(hidden.dtype))
    return pools, fills, logits.astype(jnp.float32)


@hot_path
@functools.partial(jax.jit,
                   static_argnames=("dec_model", "spec", "fused"),
                   donate_argnums=(2,))
def paged_decode_tick(dec_model, spec: PagedCacheSpec, pools, params,
                      tables, fills, toks, temps, top_ps, rngs, live,
                      done, eos, fused=False):
    """One continuous-batching decode tick over every lane of a PAGED
    pool: vmap of (cache view -> B=1 decode apply -> sample) over the
    lane axis, then ONE batched scatter of the new KV rows into their
    blocks. ``fused=False`` gathers the lane's whole table into a
    linear view (the legacy/oracle path); ``fused=True`` is the
    paged-kernel mode — attention walks only the FILLED blocks
    (`ops.paged_attention`) and the new row stages at position 0.
    Same occupancy semantics as `slot_decode_tick` — ``live`` gates
    fill advance, ``done`` is the on-device stop — expressed in paged
    form: a non-advancing lane keeps its fill (the freeze) and routes
    its dead row to the null block (the masked write). The sampling
    epilogue is `slot_decode_tick`'s: the vmap returns the lanes'
    logits and keys, and `sample_lanes` does on the batch only the
    work some lane needs (argmax | draw | nucleus sort)."""

    def one(table, fill, tok, rng):
        variables = _paged_cache_vars(spec, pools, params, table,
                                      fill, 1, fused)
        (hidden, embed), mut = dec_model.apply(
            variables, tok[None, None],
            return_hidden=True, mutable=["cache"])
        rows = [r[0] for r in _paged_new_rows(
            spec, mut["cache"], jnp.int32(0) if fused else fill, 1)]
        logits = jnp.einsum("d,vd->v", hidden[0, -1],
                            embed.astype(hidden.dtype))
        rng, r = jax.random.split(rng)
        return rows, logits.astype(jnp.float32), rng, r

    rows, logits, rngs, keys = jax.vmap(one)(tables, fills, toks, rngs)
    nxt = sample_lanes(logits, temps, top_ps, keys).astype(toks.dtype)
    # the INPUT done: a lane that emits eos this tick still writes
    # this tick's row and advances past it
    adv = live & ~done
    emit = jnp.where(done, eos.astype(toks.dtype), nxt)
    done = done | (emit == eos)
    bs = spec.block_size
    # A lane at the P + max_new - 1 == max_len boundary gets one
    # pipelined extra tick with fill == max_len: the table lookup
    # indexes one past the row, take_along_axis's default fill mode
    # yields an out-of-range id, and the scatter below silently DROPS
    # that write (out-of-bounds scatter indices drop) — the surplus
    # token was headed for the discard pile anyway. Keep the fill
    # mode: a clip mode here would instead overwrite the lane's last
    # real block.
    owner = jnp.take_along_axis(tables, (fills // bs)[:, None],
                                axis=1)[:, 0]
    bids = jnp.where(adv, owner, 0)          # masked lanes -> null
    offs = fills % bs
    pools = _paged_scatter(spec, pools, rows, bids, offs)
    fills = jnp.where(adv, fills + 1, fills)
    return pools, emit, rngs, done, fills


# ---------------------------------------------------------------------------
# Speculative decoding in the slot tick (the device surface of
# `models.speculative` generalized to the serving pools).
#
# `generate_speculative` is a batch-1 host loop; serving needs the
# draft-verify round BATCHED over every decode lane with per-lane
# variable acceptance. One jitted ROUND per scheduler step replaces
# the S=1 tick for greedy requests: the draft proposes k tokens per
# lane (a device-chained scan — k+1 ticks, the extra one warming the
# draft cache for full acceptance), the target verifies each lane's
# whole [pending, p_1..p_k] block in ONE chunked append (the same
# S>1-onto-non-empty-cache path prefill chunks ride), acceptance and
# eos truncation are computed ON DEVICE, and both caches rewind by
# setting the per-lane index leaves — rejected rows become invisible
# to the masks and are overwritten by later appends (the linear
# rewind trick; in paged form the stale scattered rows land in
# reserved blocks and are equally invisible). Between 1 and k+1
# tokens retire per round per lane; greedy acceptance makes the
# emitted stream EXACTLY the target's greedy decode, so every pinned
# token-exact contract (vs `generate`, vs the non-spec engine, under
# forced-prefix migration) holds bitwise.
# ---------------------------------------------------------------------------

def _index_leaves(cache):
    """The per-lane index vectors of a slot cache, flatten order —
    captured before a speculative round so the rewind can restore
    pre-round + n_emit exactly."""
    from jax.tree_util import tree_flatten_with_path
    flat, _ = tree_flatten_with_path(cache)
    return [leaf for path, leaf in flat if "index" in str(path)]


def _rewind_indices(cache, pre, delta):
    """Set every per-lane index leaf to ``pre + delta`` (the
    speculative rewind: pre-round fill plus the tokens the round
    actually consumed; 0 delta freezes a masked lane). KV bytes past
    the rewound index are stale but invisible — every decode mask
    attends positions < index only, and the next append overwrites
    them (the same contract `models.speculative._rewind` relies on)."""
    from jax.tree_util import tree_flatten_with_path, tree_unflatten
    flat, treedef = tree_flatten_with_path(cache)
    out, pi = [], 0
    for path, leaf in flat:
        if "index" in str(path):
            out.append((pre[pi] + delta).astype(leaf.dtype))
            pi += 1
        else:
            out.append(leaf)
    return tree_unflatten(treedef, out)


def _spec_draft_chain(drf_model, drf_params, drf_cache, toks, adv, k):
    """k+1 vmapped draft ticks, device-chained (no host sync): tick j
    feeds the previous greedy pick, so the chain proposes p_1..p_k
    (the k+1-th pick is discarded — that tick exists to write p_k's
    K/V, which a FULL acceptance needs in the draft cache; partial
    acceptances rewind it away). Masked lanes ride with frozen
    indices. Returns (drf_cache, proposals [L, k+1])."""

    def tick(carry, _):
        dcache, cur = carry

        def one(sub, tok, lv):
            (hidden, embed), mut = drf_model.apply(
                {"params": drf_params, "cache": sub}, tok[None, None],
                return_hidden=True, mutable=["cache"])
            new = _freeze_cache_indices(mut["cache"], sub, lv)
            logits = jnp.einsum("d,vd->v", hidden[0, -1],
                                embed.astype(hidden.dtype))
            return new, jnp.argmax(logits, -1).astype(tok.dtype)

        dcache, nxt = jax.vmap(one)(dcache, cur, adv)
        return (dcache, nxt), nxt

    (drf_cache, _), props = lax.scan(tick, (drf_cache, toks), None,
                                     length=k + 1)
    return drf_cache, jnp.swapaxes(props, 0, 1)        # [L, k+1]


def _spec_accept(props, greedy, pending, adv, done, eos, k: int):
    """The acceptance rule, batched: per lane, the longest prefix of
    ``props`` matching the target's greedy picks, plus the target's
    own next token — truncated at the first emitted eos (on-device
    stop, mirroring the tick's done semantics: a done lane re-emits
    eos once and never advances). Returns (emitted [L, k+1] — first
    n_emit columns are the round's tokens, later columns padding —
    n_emit [L], done, next pending token [L], proposed [L])."""
    match = props == greedy[:, :k]
    a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    jj = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    g_at_a = jnp.take_along_axis(greedy, a[:, None], axis=1)  # [L, 1]
    props_pad = jnp.concatenate([props, props[:, :1]], axis=1)
    emitted = jnp.where(jj < a[:, None], props_pad,
                        jnp.where(jj == a[:, None], g_at_a,
                                  jnp.zeros_like(g_at_a)))
    n = a + 1
    hit = (emitted == eos) & (jj <= a[:, None])
    eos_idx = jnp.min(jnp.where(hit, jj, k + 1), axis=1)
    n = jnp.minimum(n, eos_idx + 1)
    new_done = done | (adv & (eos_idx <= a))
    # Done-but-unretired lanes mirror the tick: one eos re-emit, no
    # advance. Non-live lanes emit nothing.
    n = jnp.where(adv, n, jnp.where(done, 1, 0))
    emitted = jnp.where((~adv & done)[:, None] & (jj == 0),
                        eos.astype(emitted.dtype), emitted)
    last = jnp.take_along_axis(
        emitted, jnp.clip(n - 1, 0, k)[:, None], axis=1)[:, 0]
    toks_out = jnp.where(adv, last,
                         jnp.where(done, eos.astype(pending.dtype),
                                   pending)).astype(pending.dtype)
    proposed = jnp.where(adv, k, 0)
    return emitted, n, new_done, toks_out, proposed


@hot_path
@functools.partial(jax.jit,
                   static_argnames=("dec_model", "drf_model", "k"),
                   donate_argnums=(4, 5))
def slot_spec_round(dec_model, drf_model, params, drf_params, cache,
                    drf_cache, toks, live, done, eos, k):
    """One speculative draft-verify round over every LINEAR slot lane
    (greedy only — the spec-serving contract). Returns ``(cache,
    drf_cache, emitted [L, k+1], n_emit [L], done, toks, proposed)``;
    each live lane retires 1..k+1 tokens, bitwise the target's greedy
    stream."""
    adv = live & ~done
    pre_t = _index_leaves(cache)
    pre_d = _index_leaves(drf_cache)
    drf_cache, props = _spec_draft_chain(drf_model, drf_params,
                                         drf_cache, toks, adv, k)
    block = jnp.concatenate([toks[:, None], props[:, :k]], axis=1)

    def verify(sub, row, lv):
        (hidden, embed), mut = dec_model.apply(
            {"params": params, "cache": sub}, row[None, :],
            return_hidden=True, mutable=["cache"])
        new = _freeze_cache_indices(mut["cache"], sub, lv)
        logits = jnp.einsum("sd,vd->sv", hidden[0],
                            embed.astype(hidden.dtype))
        return new, jnp.argmax(logits, -1).astype(row.dtype)

    cache, greedy = jax.vmap(verify)(cache, block, adv)
    emitted, n_emit, done, toks, proposed = _spec_accept(
        props[:, :k], greedy, toks, adv, done, eos, k)
    delta = jnp.where(adv, n_emit, 0)
    cache = _rewind_indices(cache, pre_t, delta)
    drf_cache = _rewind_indices(drf_cache, pre_d, delta)
    return cache, drf_cache, emitted, n_emit, done, toks, proposed


@hot_path
@functools.partial(jax.jit,
                   static_argnames=("dec_model", "drf_model", "spec",
                                    "k", "fused"),
                   donate_argnums=(5, 6))
def paged_spec_round(dec_model, drf_model, spec: PagedCacheSpec,
                     params, drf_params, pools, drf_cache, tables,
                     fills, toks, live, done, eos, k, fused=False):
    """The paged twin of `slot_spec_round`: the draft rides its own
    linear slot cache (small model — the paging win is the target's),
    the verify is a vmapped S=k+1 paged append (gathered view or the
    block-walking kernel path, per ``fused``), the k+1 new rows per
    lane scatter into their blocks, and the rewind is just the fills
    vector — stale rows beyond it sit in the lane's RESERVED blocks,
    invisible to every mask and overwritten by later appends (block
    reservations already cover prompt + max_new; the engine's
    spec-mode submit bound keeps even the k-token overshoot inside
    max_len, and out-of-table writes drop, per `paged_decode_tick`'s
    boundary contract)."""
    adv = live & ~done
    pre_d = _index_leaves(drf_cache)
    drf_cache, props = _spec_draft_chain(drf_model, drf_params,
                                         drf_cache, toks, adv, k)
    block = jnp.concatenate([toks[:, None], props[:, :k]], axis=1)

    def verify(table, fill, row):
        variables = _paged_cache_vars(spec, pools, params, table,
                                      fill, k + 1, fused)
        (hidden, embed), mut = dec_model.apply(
            variables, row[None, :],
            return_hidden=True, mutable=["cache"])
        rows = _paged_new_rows(spec, mut["cache"],
                               jnp.int32(0) if fused else fill, k + 1)
        logits = jnp.einsum("sd,vd->sv", hidden[0],
                            embed.astype(hidden.dtype))
        return rows, jnp.argmax(logits, -1).astype(row.dtype)

    rows, greedy = jax.vmap(verify)(tables, fills, block)
    emitted, n_emit, done, toks, proposed = _spec_accept(
        props[:, :k], greedy, toks, adv, done, eos, k)
    # The draft cache rewinds like the linear round's: without it the
    # draft index would creep k+1 per round regardless of acceptance
    # (wrong RoPE offsets, attention over rejected-token KV —
    # acceptance decays toward chance and the index eventually
    # overruns draft max_len). Output would STAY bitwise (the verify
    # decides every token) — only the speedup would silently rot.
    drf_cache = _rewind_indices(drf_cache, pre_d,
                                jnp.where(adv, n_emit, 0))
    bs = spec.block_size
    pos = fills[:, None] + jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    # Same boundary semantics as the tick: take_along_axis's fill
    # mode turns past-the-table lookups into out-of-range ids whose
    # scatter writes DROP (only ever overshoot rows), and masked
    # lanes route every row to the null block.
    owner = jnp.take_along_axis(tables, pos // bs, axis=1)
    bids = jnp.where(adv[:, None], owner, 0)
    offs = pos % bs
    pools = _paged_scatter(spec, pools, rows, bids, offs)
    fills = fills + jnp.where(adv, n_emit, 0)
    return (pools, fills, drf_cache, emitted, n_emit, done, toks,
            proposed)


@hot_path
@functools.partial(jax.jit, static_argnames=("dec_model",),
                   donate_argnums=(2,))
def slot_prefill_advance(dec_model, params, cache, slot, chunk,
                         count=None):
    """Draft-cache prompt advance: `slot_prefill_chunk` (``count``
    too) minus the LM-head matmul — spec decode only needs the draft's
    KV warm, its logits are never read during prefill (the FIRST token
    is always the target's)."""
    sub = jax.tree.map(lambda l: l[slot], cache)
    _, mut = dec_model.apply({"params": params, "cache": sub},
                             chunk[None, :], return_hidden=True,
                             count=count, mutable=["cache"])
    return jax.tree.map(lambda l, s: l.at[slot].set(s), cache,
                        mut["cache"])


@functools.partial(jax.jit, donate_argnums=(0,))
def paged_copy_block(pools, src, dst):
    """Device-side block copy (every KV leaf) — the copy-on-write
    primitive: before a lane appends into a block whose refcount > 1
    (a forked sequence sharing its tail), the allocator gives it a
    private copy and this materializes the bytes."""
    return [p.at[dst].set(p[src]) for p in pools]


def serving_params(params, dtype=jnp.bfloat16):
    """Cast the big (ndim >= 2) float params to the serving dtype.

    Params are STORED f32 (training master weights); the modules cast
    to the compute dtype at every use. Under the decode scan that cast
    sits inside the loop, so unless XLA hoists it the chip re-reads
    the f32 bytes every tick — double the weight HBM traffic decode is
    bound by. Pre-casting pins the win host-side: matrices and the
    embedding land bf16 (each use site's `astype` becomes a no-op — at
    rope archs the tokens are bit-identical, oracle-tested), while 1-D
    params (LayerNorm/RMSNorm scales, biases) stay f32 for their
    higher-precision epilogues. int8-quantized trees
    (`quantize_lm_params`) already store int8 + f32 scales; the scales
    are 1-D so this is a safe no-op on top.
    """
    def cast(p):
        if p.ndim >= 2 and jnp.issubdtype(p.dtype, jnp.floating):
            return p.astype(dtype)
        return p
    return jax.tree.map(cast, params)


def lm_param_specs(model: TransformerLM, rng, sample_tokens):
    """PartitionSpec pytree for the model's params (for inspection/tests)."""
    variables = jax.eval_shape(model.init, rng, sample_tokens)
    return param_specs(variables["params"])
