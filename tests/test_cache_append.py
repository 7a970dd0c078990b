"""The decode step's in-place row append (`ops.flash_attention.
flash_cache_append`) on the CPU in interpret mode: bitwise against the
update `ParallelSelfAttention._cache_write` makes through XLA, the
`custom_vmap` entry the serving tick reaches it through, what
`decode_attention_plan(...).write` says of every path, and a served
stream through it. What Mosaic says of the same call at the serving
cells' shapes is in `tests/test_tpu_compile.py`; what the chip says,
in PERF.md.
"""

import flax.linen as nn
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import TransformerLM
from horovod_tpu.ops.flash_attention import (
    decode_attention_plan, flash_cache_append, kv_pack,
)
from horovod_tpu.parallel.tensor import ParallelSelfAttention, unbox
from horovod_tpu.serving import ServingEngine

D, W = 128, 32


class XlaWrite(ParallelSelfAttention):
    """`_cache_write` alone, on the cache it is handed."""

    @nn.compact
    def __call__(self, k_new, v_new):
        key, value, index = (
            self.variable("cache", n, lambda: None) for n in
            ("cached_key", "cached_value", "cache_index"))
        self._cache_write(key, value, None, None, index, k_new, v_new,
                          index.value, 1, key.value.shape[-3])


def xla_write(k_cache, v_cache, k_new, v_new, index, ring):
    """One [B, W, Hkv, D] cache (as stored: a head of 64 has two KV
    heads to a row) after `_cache_write`: the linear cache's
    `dynamic_update_slice` at ``index``, or the ring's update of slot
    ``index mod W``."""
    _, mut = XlaWrite(
        num_heads=k_new.shape[-2], head_dim=k_new.shape[-1], decode=True,
        window=W if ring else None).apply(
        {"cache": {"cached_key": k_cache, "cached_value": v_cache,
                   "cache_index": index}}, k_new, v_new,
        mutable=["cache"])
    return mut["cache"]["cached_key"], mut["cache"]["cached_value"]


# positions: the first, one inside a tile, a tile's last and first,
# the cache's last, and one past it (a lane frozen at a full cache:
# `dynamic_update_slice` clamps it onto the last)
LINEAR = [0, 5, 15, 16, W - 1, W]
CASES = {f"{dt}-hkv{h}": dict(dtype=dt, Hkv=h, index=LINEAR)
         for dt in ("bfloat16", "float32") for h in (1, 2, 8)}
CASES.update({
    # a ring, some lanes past one lap and past two
    "ring": dict(dtype="bfloat16", Hkv=2, ring=True,
                 index=[0, W - 1, W, W + 7, 2 * W + 21, 3]),
    "ring-f32-hkv8": dict(dtype="float32", Hkv=8, ring=True,
                          index=[W + 1, 5 * W - 1]),
    # `generate`: B rows at one scalar index, no vmap
    "rows-at-one-index": dict(dtype="bfloat16", Hkv=2, index=17, rows=3),
    "rows-past-the-end": dict(dtype="float32", Hkv=1, index=W, rows=2),
    # heads of 64, two to a stored row of 128: granite's 8 KV heads
    # are 4 rows a position, 4 positions a tile
    "packed-bfloat16-hkv8": dict(dtype="bfloat16", Hkv=8, D=64,
                                 index=LINEAR),
    "packed-float32-hkv2": dict(dtype="float32", Hkv=2, D=64,
                                index=LINEAR),
    "packed-ring": dict(dtype="bfloat16", Hkv=4, D=64, ring=True,
                        index=[0, W - 1, W, 2 * W + 21]),
    "packed-rows-at-one-index": dict(dtype="bfloat16", Hkv=2, D=64,
                                     index=17, rows=3),
})


@pytest.mark.parametrize("case", sorted(CASES))
def test_append_is_cache_writes_update_bit_for_bit(case):
    """Whole caches compared: the rows written and every other row."""
    c = dict(CASES[case])
    dtype, Hkv, ring = jnp.dtype(c["dtype"]), c["Hkv"], c.get("ring", False)
    index = jnp.asarray(c["index"], jnp.int32)
    lanes = c.get("rows") or len(c["index"])
    r = np.random.RandomState(len(case))

    def rand(*shape):
        return jnp.asarray(r.randn(*shape), dtype)

    def slot_of(i):
        return i % W if ring else i

    d = c.get("D", D)
    stored = (W, Hkv * d // D, D)       # `kv_pack` heads a row
    assert stored[1:] == (Hkv // kv_pack(Hkv, d), d * kv_pack(Hkv, d))
    if "rows" in c:
        kc, vc = rand(lanes, *stored), rand(lanes, *stored)
        kn, vn = rand(lanes, 1, Hkv, d), rand(lanes, 1, Hkv, d)
        want = xla_write(kc, vc, kn, vn, index, ring)
        got = jax.jit(flash_cache_append)(kc, vc, kn, vn, slot_of(index))
    else:
        # the tick's view: a slot axis over B = 1 caches, vmapped
        kc, vc = rand(lanes, 1, *stored), rand(lanes, 1, *stored)
        kn, vn = rand(lanes, 1, 1, Hkv, d), rand(lanes, 1, 1, Hkv, d)
        want = jax.vmap(lambda *a: xla_write(*a, ring))(
            kc, vc, kn, vn, index)
        tick = jax.vmap(lambda kc, vc, kn, vn, i: flash_cache_append(
            kc, vc, kn, vn, slot_of(i)))
        got = jax.jit(tick)(kc, vc, kn, vn, index)
        # the batch rule fired: one call with the lanes merged, where
        # the default batching (like the scatter's) loops over lanes
        jaxpr = str(jax.make_jaxpr(tick)(kc, vc, kn, vn, index))
        assert jaxpr.count("pallas_call") == 1 and "while" not in jaxpr
    for g, w, old in zip(got, want, (kc, vc)):
        assert g.dtype == dtype and g.shape == old.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
        assert (np.asarray(g, np.float32)
                != np.asarray(old, np.float32)).any()


QWEN = dict(lanes=32, W=4096, H=12, Hkv=2, D=128)
WRITE = {
    "qwen": (dict(on_tpu=True), "kernel", "16 rows"),
    "solar": (dict(on_tpu=True, lanes=128, W=2048, H=64, Hkv=8),
              "kernel", "16 rows"),
    "laguna-ring": (dict(on_tpu=True, lanes=64, W=512, H=72, Hkv=8,
                         ring=True), "kernel", "16 rows"),
    "f32": (dict(on_tpu=True, itemsize=4), "kernel", "8 rows"),
    "forced-off-chip": (dict(impl="pallas"), "kernel", "16 rows"),
    # a tile of whole positions is lcm(16, 3) = 48 rows; 40 x 3 = 120
    "no-tile-fits": (dict(impl="pallas", W=40, H=6, Hkv=3), "xla",
                     "divides a cache of 40 x 3 rows"),
    "cpu": (dict(on_tpu=False), "xla", "only the kernel path"),
    "int8-kv": (dict(on_tpu=True, quantized=True), "xla", "only the"),
    "mesh": (dict(on_tpu=True, trivial_mesh=False), "xla", "only the"),
    "chunk": (dict(on_tpu=True, S=128), "xla", "only the"),
    "verify-block": (dict(impl="pallas", S=4), "xla", "only the"),
    "forced-lax": (dict(on_tpu=True, impl="lax"), "xla", "only the"),
    # two heads of 64 to a row: 1 row a position here, 4 at granite
    "head-dim-64": (dict(on_tpu=True, D=64), "kernel", "16 rows"),
    "granite": (dict(on_tpu=True, lanes=64, W=2048, H=32, Hkv=8, D=64),
                "kernel", "16 rows"),
    "head-dim-64-odd-kv": (dict(on_tpu=True, D=64, H=12, Hkv=3), "xla",
                           "only the"),
    "head-dim-96": (dict(on_tpu=True, D=96), "xla", "only the"),
}


@pytest.mark.parametrize("case", sorted(WRITE))
def test_plan_names_the_write(case):
    kw, write, why = WRITE[case]
    kw = dict(QWEN, **kw)
    shape = [kw.pop(k) for k in ("lanes", "W", "H", "Hkv", "D")]
    plan = decode_attention_plan(*shape, **kw)
    assert (plan.write == "kernel") <= (plan.path == "kernel"), plan
    assert plan.write == write and why in plan.write_why, plan
    assert f"write {write} ({plan.write_why})" in plan.describe()


@pytest.mark.parametrize("shape", ["tile-fits", "no-tile-fits"])
def test_step_through_the_module_is_the_lax_step(shape):
    """`ParallelSelfAttention`'s S = 1 step under the tick's vmap with
    the kernel forced: the append where a tile fits, `_cache_write`
    where none does, the same cache and index either way."""
    w, Hkv = {"tile-fits": (32, 2), "no-tile-fits": (20, 3)}[shape]
    assert (decode_attention_plan(4, w, 2 * Hkv, Hkv, D, itemsize=4,
                                  impl="pallas").write
            == ("kernel" if shape == "tile-fits" else "xla"))

    def attention(impl):
        return ParallelSelfAttention(
            num_heads=2 * Hkv, head_dim=D, num_kv_heads=Hkv, decode=True,
            chunked_prefill=True, decode_prefix_block=w,
            decode_prefix_impl=impl, out_features=16, dtype=jnp.float32)

    r = np.random.RandomState(4)
    variables = attention("lax").init(
        jax.random.PRNGKey(0), jnp.zeros((1, w, 16), jnp.float32))
    fills = [0, 7, w - 1, w]
    cache = dict(
        cached_key=jnp.asarray(r.randn(4, 1, w, Hkv, D), jnp.float32),
        cached_value=jnp.asarray(r.randn(4, 1, w, Hkv, D), jnp.float32),
        cache_index=jnp.asarray(fills, jnp.int32))
    x = jnp.asarray(r.randn(4, 1, 1, 16), jnp.float32)

    def step(impl):
        return jax.jit(jax.vmap(lambda sub, x: attention(impl).apply(
            {"params": unbox(variables["params"]), "cache": sub}, x,
            mutable=["cache"])))(cache, x)

    (want, cw), (got, cg) = step("lax"), step("pallas")
    np.testing.assert_allclose(got[:3], want[:3], atol=2e-5, rtol=2e-5)
    for name in cache:
        np.testing.assert_array_equal(cg["cache"][name], cw["cache"][name])


@pytest.mark.parametrize("cache", ["linear", "ring"])
def test_served_stream_through_the_append_is_the_lax_stream(cache):
    """The tick's wiring off the chip: 32 greedy tokens a request from
    an engine whose model forces the kernel path (interpret mode) are
    the lax model's, over a linear cache and over rings the contexts
    lap twice."""
    model = TransformerLM(
        vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=32, max_len=64, pos_emb="rope", dtype=jnp.float32,
        attn_impl="dot", decode_prefix_block=32,
        window=16 if cache == "ring" else None)
    params = unbox(model.init(jax.random.PRNGKey(3),
                              jnp.zeros((1, 8), jnp.int32))["params"])
    prompts = [np.random.RandomState(n).randint(0, 64, n).astype(np.int32)
               for n in (4, 20)]

    def serve(m):
        with ServingEngine(m, params, num_slots=2) as eng:
            streams = [np.asarray(h.result(timeout=300).tokens) for h in
                       [eng.submit(p, 32) for p in prompts]]
            return streams, eng.metrics_snapshot()

    want, lax_snap = serve(model)
    got, snap = serve(model.clone(decode_prefix_impl="pallas"))
    for g, w in zip(got, want):
        assert len(g) == 32
        np.testing.assert_array_equal(g, w)
    assert "write kernel" in snap["decode_attn_plans"]["attn"]
    assert "write xla" in lax_snap["decode_attn_plans"]["attn"]
