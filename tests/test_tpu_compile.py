"""Compile-for-TPU tests: the main path's Pallas kernels, at the
flagship LM's real widths, through the TPU compiler for a DESCRIBED
`v5e:2x2` chip (nothing is attached; nothing runs).

Interpret mode accepts block shapes and VMEM budgets real Mosaic
rejects, so every other test of these kernels can pass while the chip
refuses them. Each test here lowers + compiles one program for the
described chip and asserts the Mosaic custom call is in the compiled
text. A compile that passes is a compile, not a chip run
(`chip_smoke.py` is the chip run).

Rules this file keeps (on-chip-measurement guide §2): the topology,
shardings and shapes are built inside module-scoped fixtures — never
at import, in a `skipif`/`parametrize` argument, `autouse`, or in
conftest.py — because only ONE process may load the TPU's library and
every xdist worker imports every test file; the compiles run in the
test's own process; all of them live in this one file; and the
persistent compile cache is off around them (an entry compiled for a
described chip cannot be read back without one).
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# The flagship LM's attention shape (chip_smoke.py).
B, S, H, D = 8, 2048, 8, 128
LANES = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_config():
    """JAX as a chip run has it, not as conftest.py sets it for the
    CPU suite: x64 off (the default — under x64 the kernels' index
    scalars trace as int64, which Mosaic does not lower), and the
    persistent compile cache off (see module doc)."""
    from jax.experimental.compilation_cache import compilation_cache
    cache_was = jax.config.jax_enable_compilation_cache
    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_enable_x64", x64_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(one_chip, chip_config):
    """shape/dtype -> ShapeDtypeStruct placed on the described chip."""
    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def custom_calls(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


# The attention shapes the benchmark's cells and their neighbours use:
# (batch, seq, heads, kv heads, head_dim).
FLASH_SHAPES = {
    "flagship": (B, S, H, H, D),
    "gpt2-medium": (4, 1024, 16, 16, 64),
    "qwen2.5-1.5b-prefill": (1, 2048, 12, 2, 128),
}


def vmem_asks(fn, *args):
    """Bytes of scoped VMEM each Mosaic call of the compiled program
    was given: the kernel's `vmem_limit_bytes`, or the default where
    it set none (the record is then the default's size, or empty)."""
    from horovod_tpu.ops.flash_attention import VMEM_SCOPED_DEFAULT
    text = jax.jit(fn).lower(*args).compile().as_text()
    asks = []
    for line in text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        record = re.search(r'"scoped_memory_configs":\[([^\]]*)\]', line)
        assert record, "a Mosaic call without a scoped-memory record"
        size = re.search(r'"size":"(\d+)"', record.group(1))
        asks.append(int(size.group(1)) if size else VMEM_SCOPED_DEFAULT)
    return sorted(asks)


def planned_asks(shape, kernels):
    from horovod_tpu.ops.flash_attention import (
        VMEM_SCOPED_DEFAULT, flash_tile_check)
    b, s, h, hkv, d = shape
    plan = flash_tile_check(s, s, h, hkv, d, itemsize=2)
    assert all(ok for *_, ok in plan)
    return sorted(plan.vmem_limit_bytes[k] or VMEM_SCOPED_DEFAULT
                  for k in kernels)


def test_described_chip_is_v5_lite(topo):
    """The device_kind the peak table is keyed by."""
    from horovod_tpu.utils.profile_analysis import PEAK_BF16_FLOPS
    kind = topo.devices[0].device_kind
    assert kind == "TPU v5 lite"
    assert kind in PEAK_BF16_FLOPS


@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
def test_flash_forward(sds, shape):
    """One Mosaic call, on the tiles chosen from the shape, asking
    for the VMEM its plan said."""
    from horovod_tpu.ops.flash_attention import flash_attention
    b, s, h, hkv, d = FLASH_SHAPES[shape]
    q, kv = sds((b, s, h, d)), sds((b, s, hkv, d))

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    assert vmem_asks(fwd, q, kv, kv) == planned_asks(
        FLASH_SHAPES[shape], ["fwd"])


@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
def test_flash_fused_backward(sds, shape):
    """Forward + the two FlashAttention-2 style backward kernels:
    EXACTLY three Mosaic calls (the benchmark's `flash_roofline`
    reader counts 3 a layer), each asking for its plan's VMEM."""
    from horovod_tpu.ops.flash_attention import flash_attention
    b, s, h, hkv, d = FLASH_SHAPES[shape]
    q, kv = sds((b, s, h, d)), sds((b, s, hkv, d))

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               bwd_impl="pallas").astype(
                                   jnp.float32).mean()

    asks = vmem_asks(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert len(asks) == 3
    assert asks == planned_asks(FLASH_SHAPES[shape],
                                ["fwd", "bwd.dq", "bwd.dkv"])


def test_flash_forward_window_512(sds):
    """The banded sliding-window grid."""
    from horovod_tpu.ops.flash_attention import flash_attention
    q = sds((B, S, H, D))
    assert custom_calls(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        window=512, interpret=False),
        q, q, q) == 1


@pytest.mark.parametrize("hkv", [H, 2], ids=["mha", "gqa"])
def test_flash_decode_attention(sds, hkv):
    from horovod_tpu.ops.flash_attention import flash_decode_attention
    q, kv = sds((B, 1, H, D)), sds((B, S, hkv, D))
    assert custom_calls(
        lambda q, k, v, n: flash_decode_attention(
            q, k, v, n, block_k=256, interpret=False),
        q, kv, kv, sds((), jnp.int32)) == 1


# The serving cells' attention shapes: (lanes, cache positions, model
# fields). Two layers, a narrow MLP and a small vocabulary: the tick's
# attention, cache write and aliasing do not depend on the rest.
TICK_SHAPES = {
    "qwen2.5-1.5b": (32, 4096, dict(
        num_heads=12, num_kv_heads=2, head_dim=128, pos_emb="rope",
        rope_theta=1e6, attn_bias=True, attn_out_bias=False)),
    "solar-open2-250b": (128, 2048, dict(
        num_heads=64, num_kv_heads=8, head_dim=128, hidden_size=4096,
        pos_emb="none", attn_gate=True)),
    # heads of 64 at scale 1/64: the leaves store two KV heads to a
    # 128-lane row, [2048, 4, 128] a lane (`kv_pack`); one layer (the
    # suite's clock)
    "granite-4.0-h-micro": (64, 2048, dict(
        num_heads=32, num_kv_heads=8, head_dim=64, hidden_size=2048,
        pos_emb="none", attn_scale=1 / 64, num_layers=1)),
}


def _no_loop_under_attention(text):
    """No `while` under an attention scope: neither the lax walk's nor
    the one XLA runs a scatter of one row a lane as."""
    loops = re.findall(r' while\(.*op_name="([^"]*)"', text)
    assert [n for n in loops if "/attn/" in n or "/swa/" in n] == []


def _cache_stays_in_place(compiled, text, cache):
    """Every K/V leaf aliased input to output, and none copied."""
    kv = [leaf for path, leaf in
          jax.tree_util.tree_flatten_with_path(cache)[0]
          if "cached_" in str(path)]
    kv_bytes = sum(leaf.dtype.itemsize * leaf.size for leaf in kv)
    assert compiled.memory_analysis().alias_size_in_bytes >= kv_bytes
    # a copy of a leaf would carry its element count in some shape
    elems = {str(leaf.size) for leaf in kv}
    for ln in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* copy\(", ln)
        if m:
            n = 1
            for d in m.group(1).split(","):
                n *= int(d)
            assert str(n) not in elems, ln[:200]


@pytest.mark.parametrize("cell", sorted(TICK_SHAPES))
def test_slot_decode_tick_attends_through_the_ragged_kernel(
        sds, monkeypatch, cell):
    """The fixed pool's whole tick on the DEFAULT rule (nothing
    forced): per layer TWO Mosaic calls under the attention's scope -
    the row-append and the ragged kernel - in place of the scatter's
    and the lax walk's `while`s, the donated cache still aliased input
    to output, and no copy of a K or V leaf (the walk under vmap cost
    two layout copies of each a tick; a defensive copy before the
    aliased append would cost more than the loops it replaced)."""
    from horovod_tpu.models.transformer import (
        AttnSpec, TransformerLM, decode_attention_plan, init_slot_cache,
        serving_params, slot_decode_model, slot_decode_tick)
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel.tensor import unbox

    # The rule reaches the kernels through `_auto_interpret()`, which
    # asks for the default backend - the CPU here. Steer it in the
    # test; the program grows no option for this.
    monkeypatch.setattr(flash_attention, "_auto_interpret",
                        lambda: False)
    lanes, W, fields = TICK_SHAPES[cell]
    fields = dict(fields)
    scale = fields.pop("attn_scale", None)
    if scale:
        fields["attn_specs"] = (("attn", AttnSpec(scale=scale)),)
    layers = fields.pop("num_layers", 2)
    model = TransformerLM(
        vocab_size=4096, num_layers=layers, max_len=W, norm="rmsnorm",
        mlp_impl="swiglu", mlp_hidden=1024, dtype=jnp.bfloat16,
        attn_impl="flash", **fields)
    plan = decode_attention_plan(model, lanes)
    assert plan.path == "kernel" and plan.grid[0] == lanes, plan
    pack = 128 // fields["head_dim"]
    assert plan.pack == pack
    dec = slot_decode_model(model)

    def place(tree):
        return jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)

    params = place(jax.eval_shape(
        lambda r: serving_params(unbox(model.init(
            r, jnp.zeros((1, 64), jnp.int32))["params"])),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_slot_cache(model, lanes)))
    assert cache["block_0"]["attn"]["cached_key"].shape == (
        lanes, 1, W, fields["num_kv_heads"] // pack, 128)
    vec = lambda dt: sds((lanes,), dt)  # noqa: E731
    compiled = slot_decode_tick.lower(
        dec, params, cache, vec(jnp.int32), vec(jnp.float32),
        vec(jnp.float32), sds((lanes, 2), jnp.uint32), vec(bool),
        vec(bool), sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert plan.write == "kernel", plan
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2 * layers     # attention + append
    assert all("attn._kernel_step" in ln for ln in calls)
    _no_loop_under_attention(text)
    # the sampling epilogue is one conditional on a scalar, and the
    # vocabulary-wide sort is in a branch of it: the entry computation,
    # which every tick runs, holds none (`sample_lanes`)
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    assert " conditional(" in entry
    assert " sort(" not in entry and " sort(" in text
    _cache_stays_in_place(compiled, text, cache)


def test_mixed_tick_attends_both_kinds_through_the_ragged_kernel(
        sds, monkeypatch):
    """A tick over two kinds of softmax layer at `laguna-s-2.1`'s
    shapes (64 lanes; 48 heads over a linear cache of 12288, 72 heads
    over a ring of 512, both on 8 KV heads of 128) on the DEFAULT
    rule: the append and the ragged kernel a layer, each under its
    own kind's scope - groups of 6 and of 9, a linear cache and a ring
    in one program - no `while` under either, and both caches still
    aliased input to output with no leaf copied."""
    from horovod_tpu.models.transformer import (
        AttnSpec, TransformerLM, init_slot_cache, kernel_plans,
        serving_params, slot_decode_model, slot_decode_tick)
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel.tensor import RopeSpec, unbox

    monkeypatch.setattr(flash_attention, "_auto_interpret",
                        lambda: False)
    lanes, W = 64, 12288
    model = TransformerLM(
        vocab_size=128, num_layers=2, max_len=W, norm="rmsnorm",
        mlp_impl="swiglu", mlp_hidden=1024, dtype=jnp.bfloat16,
        attn_impl="flash", hidden_size=3072, num_heads=48,
        num_kv_heads=8, head_dim=128, pos_emb="rope", attn_gate="head",
        layer_kinds=("attn", "swa"),
        attn_specs=(("attn", AttnSpec(48, None, RopeSpec(
            theta=5e5, fraction=0.5, yarn_factor=128,
            yarn_original_len=8192, scale=1.4852030263919618))),
            ("swa", AttnSpec(72, 512, RopeSpec(theta=1e4)))))
    plans = kernel_plans(model, lanes)["decode_attn"]
    assert plans["attn"].path == plans["swa"].path == "kernel", plans
    assert plans["attn"].grid == (lanes, 48)
    assert plans["swa"].grid == (lanes, 2)
    dec = slot_decode_model(model)

    def place(tree):
        return jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)

    params = place(jax.eval_shape(
        lambda r: serving_params(unbox(model.init(
            r, jnp.zeros((1, 64), jnp.int32))["params"])),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_slot_cache(model, lanes)))
    assert cache["block_1"]["swa"]["cached_key"].shape == (
        lanes, 1, 512, 8, 128)
    vec = lambda dt: sds((lanes,), dt)  # noqa: E731
    compiled = slot_decode_tick.lower(
        dec, params, cache, vec(jnp.int32), vec(jnp.float32),
        vec(jnp.float32), sds((lanes, 2), jnp.uint32), vec(bool),
        vec(bool), sds((), jnp.int32)).compile()
    assert plans["attn"].write == plans["swa"].write == "kernel", plans
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 4              # a layer: append + attention
    for scope in ("/block_0/attn/attn._decode_attention/attn._kernel_step",
                  "/block_1/swa/swa._decode_attention/swa._kernel_step"):
        assert sum(scope in ln for ln in calls) == 2, scope
    _no_loop_under_attention(text)
    _cache_stays_in_place(compiled, text, cache)


def test_latent_tick_reads_each_row_once_through_the_ragged_kernel(
        sds, monkeypatch):
    """A tick over LongCat-Flash's layer at `longcat-flash-chat`'s
    shapes (64 lanes of 4096 positions; 64 heads over ONE head-less
    row of 512 + 64, stored 640 wide) on the DEFAULT rule: a layer is
    two latent sublayers, each the in-place append and the ragged
    kernel in its latent form - one cache operand, named
    `latent_decode` - under its own scope; no `while` under either; the
    cache aliased input to output and NO leaf copied (a 576-wide leaf
    is stored position-minor by this compiler, and then costs two
    relayout copies of the whole leaf a sublayer and tick:
    `parallel.latent_attention`)."""
    from horovod_tpu.models.transformer import (
        TransformerLM, init_slot_cache, kernel_plans, serving_params,
        slot_decode_model, slot_decode_tick)
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel.latent_attention import LatentSpec
    from horovod_tpu.parallel.tensor import unbox

    monkeypatch.setattr(flash_attention, "_auto_interpret",
                        lambda: False)
    lanes, W = 64, 4096
    model = TransformerLM(
        vocab_size=128, num_layers=1, max_len=W, norm="rmsnorm",
        mlp_impl="swiglu", mlp_hidden=1024, dtype=jnp.bfloat16,
        attn_impl="flash", hidden_size=6144, num_heads=64, head_dim=128,
        pos_emb="rope", rope_theta=1e7, tied_head=False,
        layer_kinds=("mla",), latent=LatentSpec(
            q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
            q_scale=2.0, kv_scale=12 ** 0.5),
        moe_every=1, moe_impl="dropless", moe_shortcut=True,
        num_experts=512, moe_zero_experts=256, moe_k=12, moe_hidden=256,
        moe_held=(0, 4), moe_router="softmax", moe_router_bias=True,
        moe_normalize=False, moe_scale=6.0)
    plan = kernel_plans(model, lanes)["decode_attn"]["mla"]
    assert (plan.path, plan.grid, plan.write) == (
        "kernel", (lanes, 16), "kernel"), plan
    dec = slot_decode_model(model)

    def place(tree):
        return jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)

    params = place(jax.eval_shape(
        lambda r: serving_params(unbox(model.init(
            r, jnp.zeros((1, 64), jnp.int32))["params"])),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_slot_cache(model, lanes)))
    assert cache["block_0"]["mla_1"]["cached_latent"].shape == (
        lanes, 1, W, 640)
    vec = lambda dt: sds((lanes,), dt)  # noqa: E731
    compiled = slot_decode_tick.lower(
        dec, params, cache, vec(jnp.int32), vec(jnp.float32),
        vec(jnp.float32), sds((lanes, 2), jnp.uint32), vec(bool),
        vec(bool), sds((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "/mla_" in ln]
    assert len(calls) == 4              # a sublayer: append + attention
    for sub in ("mla_0", "mla_1"):
        scope = f"/block_0/{sub}/{sub}._decode_attention/"
        mine = [ln for ln in calls if scope in ln]
        assert len(mine) == 2, sub
        kernel = [ln for ln in mine if "jit(_flash_decode)/latent_decode"
                  in ln]
        assert len(kernel) == 1 and re.search(
            r"%latent_decode[\w.]* = bf16\[64,64,512\]", kernel[0])
        # ONE cache operand: scalars, q, the rows
        assert kernel[0].count("bf16[64,4096,640]") == 1
    loops = re.findall(r' while\(.*op_name="([^"]*)"', text)
    assert [n for n in loops if "/mla_" in n] == []
    _cache_stays_in_place(compiled, text, cache)


def test_plain_latent_block_beside_held_experts_lowers_for_the_chip(
        sds, monkeypatch):
    """A tick over A.X-K1's block at `a.x-k1`'s real widths (hidden
    7168, 64 lanes of 8192 positions; ONE expert layer, 12 of 192
    experts held, a vocabulary of 128: a second layer would double the
    compile, and the sampling epilogue over 4096 rows was two thirds of
    it) on the DEFAULT rules: the layer's ONE latent mixer is the
    in-place append and `latent_decode` under `block_0/mla`, its
    products `grouped_swiglu` + `grouped_matmul` under `block_0/moe` -
    the scopes
    `latent_layer_share_of_tick` and `moe_share_of_tick` match - with
    no `while` under an attention scope, nothing left of XLA's
    `ragged-dot`, and the group-limited choice and its chips count
    compiled beside them."""
    from horovod_tpu.models.transformer import (
        TransformerLM, init_slot_cache, kernel_plans, serving_params,
        slot_decode_model, slot_decode_tick)
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel.latent_attention import LatentSpec
    from horovod_tpu.parallel.tensor import RopeSpec, unbox

    monkeypatch.setattr(flash_attention, "_auto_interpret",
                        lambda: False)
    lanes, W = 64, 8192
    model = TransformerLM(
        vocab_size=128, num_layers=1, max_len=W, norm="rmsnorm",
        mlp_impl="swiglu", mlp_hidden=18432, dtype=jnp.bfloat16,
        attn_impl="flash", hidden_size=7168, num_heads=64, head_dim=128,
        pos_emb="rope", tied_head=False, layer_kinds=("mla",),
        latent=LatentSpec(
            q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
            rope=RopeSpec(theta=1e4, yarn_factor=32.0,
                          yarn_original_len=4096),
            softmax_factor=1.81326),
        moe_every=1, moe_impl="dropless",
        num_experts=192, moe_k=8, moe_hidden=2048, moe_held=(0, 12),
        moe_shared_hidden=2048, moe_router="sigmoid",
        moe_router_bias=False, moe_scale=2.5, moe_groups=(8, 4))
    plan = kernel_plans(model, lanes)["decode_attn"]["mla"]
    assert (plan.path, plan.grid, plan.write) == (
        "kernel", (lanes, 32), "kernel"), plan
    product = kernel_plans(model, lanes, 128)["moe_product"]["tick"]
    assert (product.path, product.rows) == ("kernel", 64), product
    dec = slot_decode_model(model)

    def place(tree):
        return jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)

    params = place(jax.eval_shape(
        lambda r: serving_params(unbox(model.init(
            r, jnp.zeros((1, 64), jnp.int32))["params"])),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_slot_cache(model, lanes)))
    assert cache["block_0"]["mla"]["cached_latent"].shape == (
        lanes, 1, W, 640)
    vec = lambda dt: sds((lanes,), dt)  # noqa: E731
    compiled = slot_decode_tick.lower(
        dec, params, cache, vec(jnp.int32), vec(jnp.float32),
        vec(jnp.float32), sds((lanes, 2), jnp.uint32), vec(bool),
        vec(bool), sds((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    mine = [ln for ln in calls
            if "/block_0/mla/mla._decode_attention/" in ln]
    assert len(mine) == 2, [ln[:120] for ln in mine]
    assert sum("jit(_flash_decode)/latent_decode" in ln
               and "%latent_decode" in ln.split(" = ")[0]
               for ln in mine) == 1
    moe = [ln for ln in calls if "/block_0/moe/" in ln]
    assert len(moe) == 2 and len(calls) == 4
    assert sum("%grouped_swiglu" in ln.split(" = ")[0] for ln in moe) == 1
    assert sum("%grouped_matmul" in ln.split(" = ")[0] for ln in moe) == 1
    assert "ragged-dot" not in text and "ragged_dot" not in text
    loops = re.findall(r' while\(.*op_name="([^"]*)"', text)
    assert [n for n in loops if "/mla/" in n] == []
    # the tick's last output: a row of 12 held experts' pairs + the chips
    assert "s32[1,13]" in text
    _cache_stays_in_place(compiled, text, cache)


@pytest.mark.parametrize("program", ["tick", "tail-128"])
def test_recurrent_state_beside_latent_rows_lowers_for_the_chip(
        sds, monkeypatch, program):
    """One pool with BOTH kinds of cache at `kimi-linear-48b-a3b`'s real
    widths (hidden 2304 = 18 x 128, 32 heads x 128, 128 lanes of 8192
    positions; a KDA layer with the dense FFN and an unrotated,
    unranked latent layer with 64 of 256 experts held; a vocabulary of
    128) on the DEFAULT rules. The tick: `kda_step` in place under
    `block_0/kda`, the in-place append and `latent_decode` under
    `block_1/mla`, `grouped_swiglu` + `grouped_matmul` under
    `block_1/moe` - five Mosaic calls, the scopes `kda_share_of_tick`,
    `latent_layer_share_of_tick` and `moe_share_of_tick` match -
    neither the state nor the latent leaf copied. The padded prompt
    tail: `kda_chunked` from the cached state and the absorbed walk
    over the cached rows in ONE program, both leaves aliased."""
    from horovod_tpu.models.transformer import (
        TransformerLM, init_slot_cache, kernel_plans, serving_params,
        slot_decode_model, slot_decode_tick)
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel.latent_attention import LatentSpec
    from horovod_tpu.parallel.tensor import unbox

    monkeypatch.setattr(flash_attention, "_auto_interpret",
                        lambda: False)
    lanes, W = 128, 8192
    model = TransformerLM(
        vocab_size=128, num_layers=2, max_len=W, norm="rmsnorm",
        mlp_impl="swiglu", mlp_hidden=9216, dtype=jnp.bfloat16,
        attn_impl="flash", hidden_size=2304, num_heads=32, head_dim=128,
        pos_emb="none", tied_head=False, layer_kinds=("kda", "mla"),
        kda_neg_eigval=False, mlp_only_layers=(0,),
        latent=LatentSpec(q_rank=None, kv_rank=512, nope_dim=128,
                          rope_dim=64, v_dim=128, rotate=False),
        moe_every=1, moe_impl="dropless",
        num_experts=256, moe_k=8, moe_hidden=1024, moe_held=(0, 64),
        moe_shared_hidden=1024, moe_router="sigmoid",
        moe_router_bias=True, moe_scale=2.446, moe_groups=(1, 1))
    plans = kernel_plans(model, lanes, 128)
    assert (plans["state_step"]["kda"].path,
            plans["state_step"]["kda"].grid) == ("kernel", (lanes, 1))
    attn = plans["decode_attn"]["mla"]
    assert (attn.path, attn.grid, attn.write) == (
        "kernel", (lanes, 32), "kernel"), attn
    # 128 x 8 / 256 = 4 rows an expert expected, under the ridge
    assert {k: (p.path, p.rows) for k, p in
            plans["moe_product"].items()} == {
        "tick": ("kernel", 64), "prefill": ("kernel", 64)}
    dec = slot_decode_model(model)

    def place(tree):
        return jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)

    params = place(jax.eval_shape(
        lambda r: serving_params(unbox(model.init(
            r, jnp.zeros((1, 64), jnp.int32))["params"])),
        jax.random.PRNGKey(0)))
    assert "q" in params["block_1"]["mla"]
    assert "q_a" not in params["block_1"]["mla"]
    cache = place(jax.eval_shape(lambda: init_slot_cache(model, lanes)))
    state = cache["block_0"]["kda"]["state"]
    rows = cache["block_1"]["mla"]["cached_latent"]
    assert state.shape == (lanes, 1, 32, 128, 128)
    assert rows.shape == (lanes, 1, W, 640)
    both = state.size * 4 + rows.size * 2
    if program == "tick":
        vec = lambda dt: sds((lanes,), dt)  # noqa: E731
        compiled = slot_decode_tick.lower(
            dec, params, cache, vec(jnp.int32), vec(jnp.float32),
            vec(jnp.float32), sds((lanes, 2), jnp.uint32), vec(bool),
            vec(bool), sds((), jnp.int32)).compile()
    else:
        compiled = _chunk_program(sds, program, dec, params, cache)
    text = compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes >= both
    _cache_stays_in_place(compiled, text, cache)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    named = lambda name, scope: [  # noqa: E731
        ln for ln in calls if "%" + name in ln.split(" = ")[0]
        and scope in ln]
    assert len(named("grouped_swiglu", "/block_1/moe/")) == 1
    assert len(named("grouped_matmul", "/block_1/moe/")) == 1
    assert "ragged-dot" not in text and "ragged_dot" not in text
    if program != "tick":
        # the chunkwise form and the walk: no step kernel of either kind
        assert len(calls) == 2, [ln[:120] for ln in calls]
        return
    assert len(named("kda_step", "/block_0/kda/")) == 1
    assert len(named("latent_decode",
                     "/block_1/mla/mla._decode_attention/")) == 1
    assert len([ln for ln in calls if "/block_1/mla/" in ln]) == 2
    assert len(calls) == 5, [ln[:120] for ln in calls]
    loops = re.findall(r' while\(.*op_name="([^"]*)"', text)
    assert [n for n in loops if "/mla/" in n or "/kda/" in n] == []
    made = [ln for ln in text.splitlines() if re.search(
        r"= f32\[128,(1,)?32,128,128\]\S* "
        r"(?!parameter\(|bitcast\(|get-tuple-element\()", ln)]
    assert made == [], [ln[:160] for ln in made]
    # the tick's last output: a row of 64 held experts' pairs + the chips
    assert "s32[1,65]" in text


# The serving cells' expert layers at their published widths (one
# layer, a small vocabulary): lanes, cache positions, the tick's row
# tile, model fields.
MOE_CELLS = {
    "solar-open2-250b": (128, 2048, 64, dict(
        hidden_size=4096, num_heads=64, num_kv_heads=8, head_dim=128,
        pos_emb="none", attn_gate=True, num_experts=320, moe_k=8,
        moe_hidden=1280, moe_held=(0, 40), moe_shared_hidden=1280)),
    "laguna-s-2.1": (64, 12288, 64, dict(
        hidden_size=3072, num_heads=48, num_kv_heads=8, head_dim=128,
        pos_emb="rope", attn_gate="head", num_experts=256, moe_k=10,
        moe_hidden=1024, moe_held=(0, 32), moe_shared_hidden=1024,
        moe_router="softmax", moe_scale=2.5)),
    "longcat-flash-chat": (64, 4096, 64, dict(
        hidden_size=6144, num_heads=64, head_dim=128, pos_emb="rope",
        rope_theta=1e7, tied_head=False, layer_kinds=("mla",),
        moe_shortcut=True, num_experts=512, moe_zero_experts=256,
        moe_k=12, moe_hidden=2048, moe_held=(0, 16),
        moe_router="softmax", moe_router_bias=True, moe_normalize=False,
        moe_scale=6.0)),
}


# The programs a serving cell runs: the tick, the whole chunk and the
# padded tail of the budget's 128 positions (its count a traced
# operand) - and a chunk of one token, which an engine without a budget
# still compiles.
CHUNK_PROGRAMS = ["tick", "chunk-128", "tail-128", "chunk-1"]


def _chunk_program(sds, program, dec, params, cache):
    """`slot_prefill_chunk` compiled as "chunk-<C>" (C real tokens) or
    "tail-<C>" (C positions of which a traced count are real)."""
    from horovod_tpu.models.transformer import slot_prefill_chunk
    kind, width = program.split("-")
    count = (sds((), jnp.int32),) if kind == "tail" else ()
    return slot_prefill_chunk.lower(
        dec, params, cache, sds((), jnp.int32),
        sds((int(width),), jnp.int32), *count).compile()


@pytest.mark.parametrize("program", CHUNK_PROGRAMS)
@pytest.mark.parametrize("cell", sorted(MOE_CELLS))
def test_expert_layers_stream_their_weights_through_the_kernel(
        sds, monkeypatch, cell, program):
    """The three expert cells' ticks and chunk programs (a whole chunk
    and the tail of one token) on the DEFAULT rule
    (`grouped_product_plan`): the expert layer's products are TWO
    Mosaic calls - gate | up fused, and down - under the layer's own
    scope `block_<i>/moe/`, which is what the benchmark's
    `moe_share_of_tick` matches; nothing is left of XLA's `ragged-dot`;
    and no [E, d, f] weight leaf is copied, transposed or re-laid-out
    on its way into a call (a relayout of the experts would cost more
    than the product: count what arrives WITH a part)."""
    from horovod_tpu.models.transformer import (
        TransformerLM, init_slot_cache, kernel_plans, serving_params,
        slot_decode_model, slot_decode_tick)
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel.latent_attention import LatentSpec
    from horovod_tpu.parallel.tensor import unbox

    monkeypatch.setattr(flash_attention, "_auto_interpret",
                        lambda: False)
    lanes, W, tile, fields = MOE_CELLS[cell]
    if "mla" in fields.get("layer_kinds", ()):
        fields = dict(fields, latent=LatentSpec(
            q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
            v_dim=128, q_scale=2.0, kv_scale=12 ** 0.5))
    model = TransformerLM(
        vocab_size=128, num_layers=1, max_len=W, norm="rmsnorm",
        mlp_impl="swiglu", mlp_hidden=1024, dtype=jnp.bfloat16,
        attn_impl="flash", moe_every=1, moe_impl="dropless", **fields)
    plans = kernel_plans(model, lanes, 128)["moe_product"]
    assert {p.path for p in plans.values()} == {"kernel"}, plans
    assert plans["tick"].rows == tile
    dec = slot_decode_model(model)

    def place(tree):
        return jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)

    params = place(jax.eval_shape(
        lambda r: serving_params(unbox(model.init(
            r, jnp.zeros((1, 64), jnp.int32))["params"])),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_slot_cache(model, lanes)))
    if program == "tick":
        vec = lambda dt: sds((lanes,), dt)  # noqa: E731
        compiled = slot_decode_tick.lower(
            dec, params, cache, vec(jnp.int32), vec(jnp.float32),
            vec(jnp.float32), sds((lanes, 2), jnp.uint32), vec(bool),
            vec(bool), sds((), jnp.int32)).compile()
    else:
        compiled = _chunk_program(sds, program, dec, params, cache)
    text = compiled.as_text()
    assert "ragged-dot" not in text and "ragged_dot" not in text
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "/block_0/moe/" in ln]
    assert len(calls) == 2, [ln[:120] for ln in calls]
    assert sum("%grouped_swiglu" in ln.split(" = ")[0]
               for ln in calls) == 1
    assert sum("%grouped_matmul" in ln.split(" = ")[0]
               for ln in calls) == 1
    # the experts' leaves arrive as the parameters they are
    moe = params["block_0"]["moe"]
    for name in ("w_gate", "w_up", "w_down"):
        dims = ",".join(str(n) for n in moe[name].shape)
        made = [ln for ln in text.splitlines()
                if re.search(rf"= bf16\[{dims}\]\S* (?!parameter\()", ln)]
        assert made == [], (name, [ln[:160] for ln in made])
        assert sum(f"bf16[{dims}]" in ln for ln in calls) == 1, name
    # nothing of an expert leaf's size among the program's temporaries
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < moe["w_gate"].size * 2


@pytest.mark.parametrize("program", CHUNK_PROGRAMS)
def test_kda_state_is_stepped_in_place_by_one_call_a_layer(
        sds, monkeypatch, program):
    """solar's KDA layer (64 heads x 128, 128 lanes, float32 state) on
    the DEFAULT rule (`kda_step_plan`): the tick steps the state leaf
    through ONE Mosaic call under the layer's own scope `block_0/kda/`
    (what the benchmark's `kda_share_of_tick` matches), the leaf goes
    from the parameter to the call and from the call to the result -
    nothing else makes, selects, reduces or copies a whole state, and
    the program's temporaries stay under one leaf. A chunk of one
    token is an S = 1 step too (the call, over the chunk's one lane);
    a chunk of 128 keeps the chunkwise form."""
    from horovod_tpu.models.transformer import (
        TransformerLM, init_slot_cache, kernel_plans, serving_params,
        slot_decode_model, slot_decode_tick)
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel.tensor import unbox

    monkeypatch.setattr(flash_attention, "_auto_interpret",
                        lambda: False)
    lanes, W, _, fields = MOE_CELLS["solar-open2-250b"]
    model = TransformerLM(
        vocab_size=128, num_layers=1, max_len=W, norm="rmsnorm",
        mlp_impl="swiglu", mlp_hidden=1024, dtype=jnp.bfloat16,
        attn_impl="flash", moe_every=1, moe_impl="dropless",
        layer_kinds=("kda",), **fields)
    plan = kernel_plans(model, lanes)["state_step"]["kda"]
    assert (plan.path, plan.block, plan.grid) == ("kernel", 32,
                                                  (lanes, 2)), plan
    dec = slot_decode_model(model)

    def place(tree):
        return jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)

    params = place(jax.eval_shape(
        lambda r: serving_params(unbox(model.init(
            r, jnp.zeros((1, 64), jnp.int32))["params"])),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_slot_cache(model, lanes)))
    state = cache["block_0"]["kda"]["state"]
    assert state.shape == (lanes, 1, 64, 128, 128)
    if program == "tick":
        vec = lambda dt: sds((lanes,), dt)  # noqa: E731
        compiled = slot_decode_tick.lower(
            dec, params, cache, vec(jnp.int32), vec(jnp.float32),
            vec(jnp.float32), sds((lanes, 2), jnp.uint32), vec(bool),
            vec(bool), sds((), jnp.int32)).compile()
    else:
        compiled = _chunk_program(sds, program, dec, params, cache)
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "%kda_step" in ln.split(" = ")[0]]
    assert len(calls) == (0 if program.endswith("-128") else 1)
    assert all("/block_0/kda/" in ln for ln in calls)
    # the pool's leaf is aliased input to output in every program
    assert compiled.memory_analysis().alias_size_in_bytes >= state.size * 4
    if program != "tick":
        return
    made = [ln for ln in text.splitlines() if re.search(
        r"= f32\[128,(1,)?64,128,128\]\S* "
        r"(?!parameter\(|bitcast\(|get-tuple-element\()", ln)]
    assert made == [], [ln[:160] for ln in made]
    assert compiled.memory_analysis().temp_size_in_bytes < state.size * 4


@pytest.mark.parametrize("program", CHUNK_PROGRAMS)
def test_ssm_state_is_stepped_in_place_by_one_call_a_layer(
        sds, monkeypatch, program):
    """granite's state-space layer (64 heads x 64, state 128, 64 lanes,
    float32 state [1, 128, 4096] a lane) with the four multipliers, on
    the DEFAULT rule (`ssm_step_plan`): the tick steps the
    state leaf through ONE Mosaic call under the layer's own scope
    `block_0/ssm/` (what the benchmark's `ssm_share_of_tick` matches;
    the trace prints it as `ssm_step.<n>`), the leaf goes from the
    parameter to the call and from the call to the result - nothing
    else makes, selects, reduces or copies a whole state (64 lanes of
    36 such layers are 4.8 GB: a second copy would not fit beside the
    weights), and the program's temporaries stay under one leaf. A
    chunk of one token is an S = 1 step too; a chunk of 128 keeps the
    chunkwise form."""
    from horovod_tpu.models.transformer import (
        AttnSpec, TransformerLM, init_slot_cache, kernel_plans,
        serving_params, slot_decode_model, slot_decode_tick)
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel.state_space import SsmSpec
    from horovod_tpu.parallel.tensor import unbox

    monkeypatch.setattr(flash_attention, "_auto_interpret",
                        lambda: False)
    lanes, W = 64, 2048
    model = TransformerLM(
        vocab_size=128, num_layers=1, max_len=W, norm="rmsnorm",
        mlp_impl="swiglu", mlp_hidden=8192, dtype=jnp.bfloat16,
        attn_impl="flash", hidden_size=2048, num_heads=32,
        num_kv_heads=8, head_dim=64, pos_emb="none", ln_eps=1e-5,
        layer_kinds=("ssm",),
        ssm=SsmSpec(num_heads=64, head_dim=64, state_size=128),
        attn_specs=(("attn", AttnSpec(scale=1 / 64)),),
        embed_scale=12, residual_scale=0.22, logits_divisor=8)
    plan = kernel_plans(model, lanes)["state_step"]["ssm"]
    assert (plan.path, plan.block, plan.grid) == (
        "kernel", 4096, (lanes, 1, 1)), plan
    dec = slot_decode_model(model)

    def place(tree):
        return jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)

    params = place(jax.eval_shape(
        lambda r: serving_params(unbox(model.init(
            r, jnp.zeros((1, 64), jnp.int32))["params"])),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: init_slot_cache(model, lanes)))
    state = cache["block_0"]["ssm"]["state"]
    assert state.shape == (lanes, 1, 1, 128, 4096)
    assert cache["block_0"]["ssm"]["conv_tail"].shape == (
        lanes, 1, 3, 4352)
    if program == "tick":
        vec = lambda dt: sds((lanes,), dt)  # noqa: E731
        compiled = slot_decode_tick.lower(
            dec, params, cache, vec(jnp.int32), vec(jnp.float32),
            vec(jnp.float32), sds((lanes, 2), jnp.uint32), vec(bool),
            vec(bool), sds((), jnp.int32)).compile()
    else:
        compiled = _chunk_program(sds, program, dec, params, cache)
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "%ssm_step" in ln.split(" = ")[0]]
    assert len(calls) == (0 if program.endswith("-128") else 1)
    assert all("/block_0/ssm/" in ln for ln in calls)
    # the pool's leaf is aliased input to output in every program
    assert compiled.memory_analysis().alias_size_in_bytes >= state.size * 4
    if program != "tick":
        return
    made = [ln for ln in text.splitlines() if re.search(
        r"= f32\[64,(1,)?1,128,4096\]\S* "
        r"(?!parameter\(|bitcast\(|get-tuple-element\()", ln)
        and "%ssm_step" not in ln.split(" = ")[0]]
    assert made == [], [ln[:160] for ln in made]
    assert compiled.memory_analysis().temp_size_in_bytes < state.size * 4


def test_flash_under_a_four_chip_data_mesh(topo, chip_config,
                                           monkeypatch):
    """The LM's `attn_impl="flash"` inside a GSPMD program over four
    chips. A bare Mosaic kernel there is refused ("Mosaic kernels
    cannot be automatically partitioned") — something interpret mode
    on the CPU can never show — so `make_attn_fn` hands each device
    its batch block through shard_map."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models.transformer import make_attn_fn
    from horovod_tpu.ops import flash_attention
    from horovod_tpu.parallel.mesh import make_mesh, use

    monkeypatch.setattr(flash_attention, "_auto_interpret",
                        lambda: False)
    mesh = make_mesh(devices=topo.devices, data=4)
    q = jax.ShapeDtypeStruct(
        (B, S, H, D), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data")))
    attn = make_attn_fn("flash", causal=True)

    def loss(q, k, v):
        return attn(q, k, v, None).astype(jnp.float32).mean()

    with use(mesh):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).compile().as_text()
    assert text.count("tpu_custom_call") == 3


def _scheduled_entry(text):
    """[(name, opcode)] of the scheduled module's entry computation,
    in the order the core runs it."""
    entry = text[text.index("\nENTRY "):].splitlines()
    found = (re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(", ln)
             for ln in entry)
    return [m.groups() for m in found if m]


def test_data_parallel_step_reduces_its_gradients_asynchronously(
        hvd, topo, chip_config):
    """`make_train_step` over all four chips, with the options the
    factory itself chooses: a matrix's all-reduce (reduced alone, in
    its own shape) and the small leaves' bucket (a [rows, 128] view)
    are `async-collective-start` / `-done` pairs in the scheduled
    module with compute between a start and its done - not the
    synchronous `all-reduce` instructions the same step compiled to
    before PR 44, when only the loss's scalar may stay one. Over ONE
    chip the factory chooses today's options: the combiner pin."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.ops.fusion import (combiner_override_options,
                                        step_compiler_options)
    from horovod_tpu.parallel.mesh import make_mesh

    width, depth = 1024, 4

    def loss_fn(p, x):
        h = x
        for layer in p:
            h = jnp.tanh(h @ layer["w"].astype(jnp.bfloat16)
                         + layer["b"].astype(jnp.bfloat16))
        return (h.astype(jnp.float32) ** 2).mean()

    tx = hvd.DistributedOptimizer(optax.adamw(3e-4))
    params = [{"w": jax.ShapeDtypeStruct((width, width), jnp.float32),
               "b": jax.ShapeDtypeStruct((width,), jnp.float32)}
              for _ in range(depth)]

    one = make_mesh(devices=topo.devices[:1], data=1)
    assert (step_compiler_options(one, "data")
            == combiner_override_options())

    mesh = make_mesh(devices=topo.devices, data=4)
    assert (step_compiler_options(mesh, "data").items()
            > combiner_override_options().items())
    rep = NamedSharding(mesh, P())

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=rep), tree)

    x = jax.ShapeDtypeStruct((2048, width), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    low = hvd.make_train_step(loss_fn, tx, mesh=mesh).__wrapped__.lower(
        place(params), place(jax.eval_shape(tx.init, params)), x)
    ins = _scheduled_entry(low.compile().as_text())
    names = [n for n, _ in ins]
    starts = [k for k, n in enumerate(names)
              if n.startswith("async-collective-start")]
    # every matrix at least (the biases' bucket is one more here); the
    # names are the TPU compiler's own, read on jax 0.9.0 / libtpu
    # 0.0.34 - a version that renames them fails here, not on a chip
    assert len(starts) >= depth, names
    for k in starts:
        done = names.index(names[k].replace("start", "done"))
        between = [op for _, op in ins[k + 1:done]
                   if op in ("fusion", "convolution", "custom-call")]
        assert done > k and between, (names[k], ins[k:done + 1])
    sync = [n for n, op in ins if op == "all-reduce"]
    assert len(sync) <= 1, sync        # the loss's scalar mean
