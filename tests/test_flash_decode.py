"""The ragged decode-attention kernel (`ops.flash_attention.
flash_decode_attention`) and the rule that chooses it
(`decode_attention_plan`), on the CPU in interpret mode: the kernel
against the lax walk it replaces, lane by lane; the `custom_vmap` entry
the serving tick reaches it through; the whole `slot_decode_tick` with
the kernel forced against the default (lax on the CPU) tick; and the
selection rule itself. What Mosaic says about the same shapes is in
`tests/test_tpu_compile.py`; what the chip says, in PERF.md.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import (
    TransformerLM, decode_attention_plan as model_plan, init_slot_cache,
    slot_decode_model, slot_decode_tick, slot_prefill_chunk,
)
from horovod_tpu.ops.flash_attention import (
    DecodePlan, decode_attention_plan, flash_decode_attention, kv_pack,
    unpack_kv_rows,
)
from horovod_tpu.parallel.tensor import ParallelSelfAttention, unbox

D, W, BK = 128, 512, 128
HEADS = {"12q2kv": (12, 2), "64q8kv": (64, 8), "mha4": (4, 4)}
# heads of 64: the cache stores two KV heads to a 128-lane row
# (`kv_pack`); "-scale" is granite's 1/64, folded into q against
# 64 ** -0.5 - a kernel that scales by the row's width misses it
PACKED = {"32q8kv-d64": (32, 8, 64),
          "4q2kv-d64-scale": (4, 2, 64, 1 / 64)}
# one fill a lane; "ragged" is a tick's mix (fill 1: a just-reset lane)
LENGTHS = {"one": [1], "block": [BK], "block+1": [BK + 1], "full": [W],
           "ragged": [1, BK, BK + 1, W, 37, 300]}


def attention(impl, H, Hkv, D=D, scale=None):
    return ParallelSelfAttention(
        num_heads=H, head_dim=D, num_kv_heads=Hkv, decode=True,
        chunked_prefill=True, decode_prefix_block=BK,
        decode_prefix_impl=impl, out_features=32, dtype=jnp.float32,
        softmax_scale=scale)


def lanes_state(lengths, *shape, seed=0):
    """Parameters, one random token a lane and a cache a lane whose
    prefix is filled to ``length - 1`` (the step writes the last)."""
    r = np.random.RandomState(seed)
    L = len(lengths)
    variables = attention("lax", *shape).init(
        jax.random.PRNGKey(seed), jnp.zeros((1, W, 32), jnp.float32))
    cache = jax.tree.map(
        lambda leaf: jnp.zeros((L,) + leaf.shape, leaf.dtype),
        variables["cache"])

    def kv():       # the leaf's stored shape: [L, 1, W, Hkv // pack, 128]
        return jnp.asarray(r.randn(*cache["cached_key"].shape),
                           jnp.float32)

    cache = dict(cache, cached_key=kv(), cached_value=kv(),
                 cache_index=jnp.asarray(lengths, jnp.int32) - 1)
    x = jnp.asarray(r.randn(L, 1, 1, 32), jnp.float32)
    return unbox(variables["params"]), cache, x


def step(impl, params, cache, x, *shape):
    """One S = 1 step of every lane, vmapped as the tick vmaps it."""
    def one(sub, x):
        return attention(impl, *shape).apply(
            {"params": params, "cache": sub}, x, mutable=["cache"])
    return jax.jit(jax.vmap(one))(cache, x)


@pytest.mark.parametrize("heads,lengths", [
    (h, n) for h in sorted(HEADS) for n in sorted(LENGTHS)]
    + [(h, "ragged") for h in sorted(PACKED)])
def test_kernel_matches_the_walk_lane_by_lane(heads, lengths):
    shape = {**HEADS, **PACKED}[heads]
    params, cache, x = lanes_state(LENGTHS[lengths], *shape)
    assert cache["cached_key"].shape[-2:] == (
        (shape[1] // 2, 128) if heads in PACKED else (shape[1], D))
    want, cw = step("lax", params, cache, x, *shape)
    got, cg = step("pallas", params, cache, x, *shape)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # the write the two paths share: same rows, same advanced index
    for a, b in zip(jax.tree.leaves(cg), jax.tree.leaves(cw)):
        np.testing.assert_array_equal(a, b)


def kv_case(H, Hkv, L, D=D, seed=3):
    """q and the caches as they are stored (`kv_pack` heads a row)."""
    r = np.random.RandomState(seed)
    pack = kv_pack(Hkv, D)
    q = jnp.asarray(r.randn(L, 1, H, D), jnp.float32)
    k = jnp.asarray(r.randn(L, W, Hkv // pack, D * pack), jnp.float32)
    v = jnp.asarray(r.randn(L, W, Hkv // pack, D * pack), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_vmapped_entry_matches_a_loop_over_lanes(heads):
    """`jax.vmap` over slots folds them into the lanes of ONE call
    (the custom_vmap rule); a Python loop runs a call a lane."""
    H, Hkv = HEADS[heads]
    lengths = LENGTHS["ragged"]
    q, k, v = kv_case(H, Hkv, len(lengths))
    n = jnp.asarray(lengths, jnp.int32)
    looped = jnp.concatenate([
        flash_decode_attention(q[i:i + 1], k[i:i + 1], v[i:i + 1], n[i],
                               block_k=BK) for i in range(len(lengths))])
    vmapped = jax.vmap(
        lambda q, k, v, n: flash_decode_attention(
            q[None], k[None], v[None], n, block_k=BK)[0])(q, k, v, n)
    batched = flash_decode_attention(q, k, v, n, block_k=BK)
    np.testing.assert_array_equal(vmapped, looped)
    np.testing.assert_array_equal(batched, looped)


@pytest.mark.parametrize("scale", [None, 1 / 64])
def test_packed_rows_against_the_plain_softmax(scale):
    """The entry itself at a head of 64 over rows of two KV heads,
    against softmax(scale q k^T) v on the heads unpacked: every query
    head reads its own head's half of a row, at the TRUE head's scale
    (64 ** -0.5 where none is given, not the row's 128 ** -0.5)."""
    H, Hkv, d = 8, 4, 64
    lengths = jnp.asarray([1, BK, W, 300], jnp.int32)
    q, k, v = kv_case(H, Hkv, 4, d)
    got = jax.vmap(
        lambda q, k, v, n: flash_decode_attention(
            q[None], k[None], v[None], n, block_k=BK, scale=scale)[0])(
        q, k, v, lengths)
    k, v = (jnp.repeat(unpack_kv_rows(t, 2), H // Hkv, axis=2)
            for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q * (scale or d ** -0.5), k)
    s = jnp.where(jnp.arange(W) < lengths[:, None, None, None], s, -1e30)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_vmapped_entry_is_one_call_not_a_loop():
    """The default batching of a pallas_call with a batched scalar
    prefetch is a `while` over lanes; the rule must leave none."""
    q, k, v = kv_case(4, 2, 3)
    jaxpr = str(jax.make_jaxpr(jax.vmap(
        lambda q, k, v, n: flash_decode_attention(
            q[None], k[None], v[None], n, block_k=BK)[0]))(
        q, k, v, jnp.asarray([5, 200, 512], jnp.int32)))
    assert jaxpr.count("pallas_call") == 1
    assert "while" not in jaxpr


def test_shared_length_is_the_ragged_kernel_with_the_length_broadcast():
    """`generate`'s B > 1 step: one index for every row."""
    q, k, v = kv_case(12, 2, 3)
    shared = flash_decode_attention(q, k, v, jnp.int32(130), block_k=BK)
    ragged = flash_decode_attention(q, k, v, jnp.full((3,), 130),
                                    block_k=BK)
    np.testing.assert_array_equal(shared, ragged)


def test_length_zero_is_zero_not_nan():
    q, k, v = kv_case(4, 4, 2)
    out = flash_decode_attention(q, k, v, jnp.asarray([0, 9]), block_k=BK)
    assert np.isfinite(np.asarray(out)).all()
    assert not np.asarray(out[0]).any()


# ---- the whole tick ---------------------------------------------------------

def tiny_lm(**kw):
    return TransformerLM(vocab_size=96, num_layers=2, num_heads=4,
                         num_kv_heads=2, head_dim=128, max_len=256,
                         pos_emb="rope", dtype=jnp.float32,
                         attn_impl="dot", decode_prefix_block=64, **kw)


def test_tick_with_the_kernel_forced_equals_the_default_tick():
    """Mixed fills with a frozen (mid-prefill) lane, a free lane at
    fill 0 and a done lane: tokens equal, every lane's logits within
    the flash-decode tolerance, the caches equal."""
    model = tiny_lm()
    params = unbox(jax.jit(model.init)(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"])
    r = np.random.RandomState(2)
    fills = [130, 7, 64, 0, 33]           # slot 3 free
    live = jnp.asarray([True, True, False, False, True])
    done = jnp.asarray([False, False, False, False, True])
    toks = jnp.asarray(r.randint(0, 96, 5), jnp.int32)
    rngs = jnp.stack([jax.random.PRNGKey(i) for i in range(5)])

    def run(dec):
        r2 = np.random.RandomState(5)      # the same prompts both ways
        cache = init_slot_cache(model, 5)
        for slot, n in enumerate(fills):
            done_ = 0
            while done_ < n:               # power-of-two chunks
                c = 1 << ((n - done_).bit_length() - 1)
                cache, _, _ = slot_prefill_chunk(
                    dec, params, cache, jnp.int32(slot),
                    jnp.asarray(r2.randint(0, 96, c), jnp.int32))
                done_ += c
        @jax.jit        # one program, not a compile a primitive a slot
        def slot_logits(cache, slot):
            sub = jax.tree.map(lambda leaf: leaf[slot], cache)
            (h, emb), _ = dec.apply(
                {"params": params, "cache": sub}, toks[slot][None, None],
                return_hidden=True, mutable=["cache"])
            return jnp.einsum("d,vd->v", h[0, -1], emb)

        logits = [slot_logits(cache, jnp.int32(slot)) for slot in range(5)]
        out = []
        for _ in range(3):
            cache, emit, *_ = slot_decode_tick(
                dec, params, cache, toks if not out else out[-1],
                jnp.zeros(5), jnp.ones(5), rngs, live, done,
                jnp.int32(-1))
            out.append(emit)
        return jnp.stack(out), jnp.stack(logits), cache

    want_t, want_l, want_c = run(slot_decode_model(model))
    got_t, got_l, got_c = run(slot_decode_model(
        model.clone(decode_prefix_impl="pallas")))
    np.testing.assert_array_equal(got_t, want_t)
    np.testing.assert_allclose(got_l, want_l, atol=2e-4)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got_c)[0],
                            jax.tree.leaves(want_c)):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=str(path))


# ---- the rule -----------------------------------------------------------------

QWEN = dict(lanes=32, W=4096, H=12, Hkv=2, D=128)
RULE = {
    "tpu": (dict(on_tpu=True), "kernel"),
    "cpu": (dict(on_tpu=False), "lax"),
    "int8-kv": (dict(on_tpu=True, quantized=True), "lax"),
    "mesh": (dict(on_tpu=True, trivial_mesh=False), "lax"),
    "chunk": (dict(on_tpu=True, S=16), "lax"),
    # two heads of 64 are a 128-lane row; three halves, and a head of
    # 96 or 80, are not whole rows
    "head-dim-64": (dict(on_tpu=True, D=64), "kernel", "2 heads a row"),
    "head-dim-64-odd-kv": (dict(on_tpu=True, D=64, Hkv=3), "lax",
                           "3 KV heads do not pack 2 to a row"),
    "head-dim-96": (dict(on_tpu=True, D=96), "lax", "nor a whole part"),
    "head-dim-80": (dict(on_tpu=True, D=80), "lax", "nor a whole part"),
    "no-block-divides": (dict(on_tpu=True, W=4100), "lax"),
    "forced-lax": (dict(on_tpu=True, impl="lax"), "lax"),
    "forced-kernel-off-chip": (dict(on_tpu=False, impl="pallas"), "kernel"),
    "forced-kernel-head-dim-64": (dict(on_tpu=False, impl="pallas", D=64),
                                  "kernel"),
    "forced-kernel-int8-kv": (dict(impl="pallas", quantized=True), "lax"),
    "forced-kernel-mesh": (dict(impl="pallas", trivial_mesh=False), "lax"),
    "forced-kernel-chunk": (dict(impl="pallas", S=4), "lax"),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_selection_rule(case):
    kw, path, *why = RULE[case]
    shape = dict(QWEN, **{k: kw.pop(k) for k in ("W", "Hkv", "D")
                          if k in kw})
    plan = decode_attention_plan(
        shape["lanes"], shape["W"], shape["H"], shape["Hkv"], shape["D"],
        **kw)
    assert plan.path == path, plan
    assert plan.why and all(w in plan.why for w in why), plan
    if path == "kernel":
        assert shape["W"] % plan.block_k == 0
        assert plan.grid == (32, shape["W"] // plan.block_k)
        assert plan.vmem_bytes > 0 and "block_k" in plan.describe()
    else:
        assert plan.block_k is None and plan.describe().startswith("lax")


def test_rule_rejects_an_unknown_impl():
    with pytest.raises(ValueError, match="lax\\|pallas"):
        decode_attention_plan(1, 256, 4, 4, 128, impl="cuda")


WRITE = ("kernel", "one aliased call for all lanes, a tile of 16 rows")
# the cells' attention shapes -> the plan, field for field (D 128: the
# plans as they stood before a head of 64 packed)
CELLS = {
    "qwen": ((32, 4096, 12, 2, 128), DecodePlan(
        "kernel", "S = 1 on a TPU", 1024, (32, 4), 2662400, None,
        *WRITE)),
    "solar": ((128, 2048, 64, 8, 128), DecodePlan(
        "kernel", "S = 1 on a TPU", 256, (128, 8), 4358144, None,
        *WRITE)),
    "granite": ((64, 2048, 32, 8, 64), DecodePlan(
        "kernel", "S = 1 on a TPU, 2 heads a row", 512, (64, 4),
        3227648, None, *WRITE, pack=2)),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_block_follows_the_shape(cell):
    """The serving cells' attention shapes: a K block of half a MiB,
    whatever the head count, inside Mosaic's default VMEM; granite's
    head of 64 planned at its stored rows [2048, 4, 128]."""
    shape, plan = CELLS[cell]
    assert decode_attention_plan(*shape, on_tpu=True) == plan
    assert plan.pack == 1 or f"{plan.pack} heads a row" in plan.describe()


def test_cpu_model_keeps_the_walk_and_says_why():
    """The tier-1 suite's engines: no interpret-mode kernel unless a
    test forces one."""
    assert model_plan(tiny_lm(), 4).path == "lax"
    assert model_plan(tiny_lm(decode_prefix_impl="pallas"), 4).path == \
        "kernel"
    assert "window" in model_plan(tiny_lm(window=32), 4).why
    assert "mask" in model_plan(
        tiny_lm().clone(decode_prefix_block=None), 4).why
