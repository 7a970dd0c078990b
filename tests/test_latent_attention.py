"""Latent attention (`parallel.latent_attention`) and the expert layer's
identity experts (`parallel.expert.HeldExpertsMoE.zero_experts`) as
modules, at tiny sizes on the CPU: the two forms of the one attention
function against each other and against the plain reference the
benchmark keeps (`benchmarks/arch/longcat.py`, which imports nothing of
the program); the ragged decode kernel in its latent form (interpret
mode) against the lax walk, lane by lane; the in-place append of a
latent row against XLA's update; the rule that picks the path; the
router's unnormalised weights, the bias that chooses but does not weigh,
and the shares of a cut router adding up to the uncut layer. What Mosaic
says of the kernel at the cell's shape is in `tests/test_tpu_compile.py`.
"""

import dataclasses
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness.cells import load_module
from horovod_tpu.ops.flash_attention import (
    decode_attention_plan, flash_cache_append, flash_decode_attention,
)
from horovod_tpu.parallel.expert import HeldExpertsMoE, grouped_experts
from horovod_tpu.parallel.latent_attention import (
    LatentAttention, LatentSpec, chunk_form, latent_walk,
)
from horovod_tpu.parallel.tensor import RopeSpec, apply_rope, unbox

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
A = load_module(os.path.join(REPO, "benchmarks", "arch", "longcat.py"),
                "arch_longcat_for_module_tests")
with open(os.path.join(REPO, "tests", "benchmark", "tiny",
                       "tiny-longcat.json")) as f:
    ARCH = json.load(f)["arch"]     # hidden 64, 4 heads, ranks 32 / 16
SPEC = LatentSpec(q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16,
                  q_scale=2.0 ** 0.5, kv_scale=2.0)
W = 64


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def layer(impl=None, **kw):
    return LatentAttention(
        num_heads=4, spec=SPEC, out_features=64, rope_theta=1e4,
        norm_eps=1e-5, dtype=jnp.float32, decode=True,
        chunked_prefill=True, decode_prefix_block=16,
        decode_prefix_impl=impl, **kw)


@pytest.fixture(scope="module")
def state():
    """(parameters, an empty cache of W positions)."""
    variables = layer().init(jax.random.PRNGKey(0),
                             jnp.zeros((1, W, 64), jnp.float32))
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(a.size),
                                               a.shape, a.dtype),
        unbox(variables["params"]))
    return params, variables["cache"]


def xs(n, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, n, 64),
                             jnp.float32)


# ---- the spec and the rule -----------------------------------------------
def test_spec_counts_and_the_form_a_chunk_takes():
    pub = LatentSpec(q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
                     v_dim=128)
    assert (pub.row, pub.stored) == (576, 640)
    assert pub.softmax_scale == 192 ** -0.5
    assert (SPEC.row, SPEC.stored) == (24, 128)
    # the two forms cross at S = 171 at the published widths
    assert chunk_form(1, 64, pub) == chunk_form(128, 64, pub) == "absorbed"
    assert chunk_form(170, 64, pub) == "absorbed"
    assert chunk_form(171, 64, pub) == "expanded"


def test_the_plan_says_which_path_a_latent_layer_takes_and_why():
    on = decode_attention_plan(64, 4096, 64, 1, 640, itemsize=2,
                               on_tpu=True, latent=512)
    assert (on.path, on.grid, on.write) == ("kernel", (64, 16), "kernel")
    assert "latent rows of 640 read once" in on.why
    assert "512-wide values" in on.describe()
    # one cache operand: the plan's VMEM has one streamed block, not two
    kv = decode_attention_plan(64, 4096, 64, 1, 640, itemsize=2,
                               on_tpu=True)
    assert on.vmem_bytes == kv.vmem_bytes - 2 * on.block_k * 640 * 2
    off = decode_attention_plan(4, W, 4, 1, 128, latent=16)
    assert (off.path, off.why) == ("lax", "not on a TPU")
    odd = decode_attention_plan(4, W, 4, 1, 128, on_tpu=True, latent=16)
    assert odd.path == "lax" and "value width 16" in odd.why
    chunk = decode_attention_plan(4, W, 4, 1, 128, S=8, on_tpu=True,
                                  latent=16)
    assert chunk.path == "lax"
    with pytest.raises(ValueError, match="no v_cache"):
        flash_decode_attention(jnp.zeros((1, 1, 4, 128)),
                               jnp.zeros((1, W, 1, 128)), None, 1)


def test_interleaved_rotation_is_the_reference_s():
    x = jax.random.normal(jax.random.PRNGKey(3), (12, 3, 8))
    pos = jnp.arange(5, 17)
    got = apply_rope(x, pos, 1e4, interleaved=True)
    want = A.rotate(dict(ARCH, rope_theta=1e4), x, pos)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # the half-split layout is another function, and is what it was
    half = apply_rope(x, pos, 1e4)
    assert float(jnp.abs(half - got).max()) > 0.1


# ---- one function, two forms ------------------------------------------------
def test_expanded_forward_equals_the_reference(state):
    params, _ = state
    arch = dict(ARCH, rope_theta=1e4, mla_scale_q_lora=True,
                mla_scale_kv_lora=True)
    assert A.scales(arch) == pytest.approx((SPEC.q_scale, SPEC.kv_scale))
    x = xs(48)
    # the layer one program, not a compile a primitive
    got = jax.jit(layer().clone(decode=False).apply)(
        {"params": params}, x)[0]
    np.testing.assert_allclose(got, A.mla(arch, params, x[0]), atol=2e-5)


def test_absorbed_steps_and_chunks_equal_the_expanded_forward(state):
    """A prompt through chunks (absorbed: S <= 170) and S = 1 steps
    (absorbed) is the full forward pass (expanded), row by row; the
    one-pass prefill (expanded) leaves the same cache as the chunks."""
    params, empty = state
    x = xs(40)
    # (the chunks and the one pass stay op by op: their caches are
    # compared to 1e-6, which two differently fused programs miss)
    want = jax.jit(layer().clone(decode=False).apply)(
        {"params": params}, x)

    def run(cache, rows):
        y, mut = layer().apply({"params": params, "cache": cache}, rows,
                               mutable=["cache"])
        return y, mut["cache"]

    out, cache = [], empty
    for lo, hi in ((0, 16), (16, 24), (24, 25), (25, 33)):  # chunks, a step
        y, cache = run(cache, x[:, lo:hi])
        out.append(y)
    for t in range(33, 40):
        y, cache = run(cache, x[:, t:t + 1])
        out.append(y)
    np.testing.assert_allclose(jnp.concatenate(out, 1), want, atol=2e-5)
    assert int(cache["cache_index"]) == 40
    rows = np.asarray(cache["cached_latent"])
    assert rows.shape == (1, W, SPEC.stored)
    assert not rows[:, 40:].any() and not rows[..., SPEC.row:].any()
    one_pass = layer().clone(chunked_prefill=False)
    y, mut = one_pass.apply({"params": params, "cache": empty}, x,
                            mutable=["cache"])
    np.testing.assert_allclose(y, want, atol=2e-5)
    np.testing.assert_allclose(mut["cache"]["cached_latent"], rows,
                               atol=1e-6)


def test_the_walk_s_two_forms_agree_on_a_chunk(state):
    """`latent_walk` absorbed against expanded on S = 8 rows over a
    filled prefix: the same numbers either way (the rule picks by
    cost alone)."""
    params, _ = state
    r = np.random.RandomState(2)
    rows = jnp.asarray(r.randn(1, W, SPEC.stored), jnp.float32)
    rows = rows.at[..., SPEC.row:].set(0)
    q = jnp.asarray(r.randn(1, 8, 4, SPEC.nope_dim + SPEC.rope_dim),
                    jnp.float32) * SPEC.softmax_scale
    k_up, v_up = params["k_up"], params["v_up"]
    exp = latent_walk(q, rows, jnp.int32(20), spec=SPEC, block=16,
                      expand=(k_up, v_up))
    qa = jnp.concatenate(
        [jnp.einsum("...shn,rhn->...shr", q[..., :SPEC.nope_dim], k_up),
         q[..., SPEC.nope_dim:],
         jnp.zeros((1, 8, 4, SPEC.stored - SPEC.row))], -1)
    ab = latent_walk(qa, rows, jnp.int32(20), spec=SPEC, block=16)
    ab = jnp.einsum("...shr,rhv->...shv", ab, v_up)
    np.testing.assert_allclose(ab, exp, atol=2e-5)


# ---- the kernel in its latent form ---------------------------------------------
LENGTHS = [0, 1, 16, 17, W, 37]     # a lane that holds nothing among them


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_kernel_matches_the_walk_lane_by_lane(dtype):
    """64 query heads' worth of structure at a toy width: H heads of
    D = 256 over ONE row of 256 whose first 128 are the values too,
    ragged lengths (0: one masked block, a finite result)."""
    H, D, Dv = 8, 256, 128
    spec = LatentSpec(q_rank=8, kv_rank=Dv, nope_dim=8, rope_dim=D - Dv,
                      v_dim=8)
    r = np.random.RandomState(0)
    L = len(LENGTHS)
    pool = jnp.asarray(r.randn(L, W, 1, D), dtype)
    q = jnp.asarray(r.randn(L, 1, H, D), dtype)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    got = flash_decode_attention(q, pool, None, lengths, block_k=16,
                                 scale=spec.softmax_scale, latent=Dv,
                                 interpret=True)
    assert got.shape == (L, 1, H, Dv) and got.dtype == q.dtype
    assert np.isfinite(np.asarray(got, np.float32)).all()
    qs = q * jnp.asarray(spec.softmax_scale, q.dtype)
    for lane, n in enumerate(LENGTHS):
        if n == 0:
            continue
        want = latent_walk(qs[lane], pool[lane, :, 0], jnp.int32(n - 1),
                           spec=spec, block=16)
        tol = 2e-5 if dtype == "float32" else 3e-2
        np.testing.assert_allclose(
            np.asarray(got[lane], np.float32), np.asarray(want), atol=tol,
            rtol=tol)


def test_latent_kernel_under_the_tick_s_vmap_joins_the_lanes():
    H, D, Dv = 4, 128, 128
    r = np.random.RandomState(1)
    pool = jnp.asarray(r.randn(5, 1, W, 1, D), jnp.float32)
    q = jnp.asarray(r.randn(5, 1, 1, H, D), jnp.float32)
    lengths = jnp.asarray([3, 64, 1, 20, 40], jnp.int32)

    def one(q, pool, n):
        return flash_decode_attention(q, pool, None, n, block_k=16,
                                      scale=0.1, latent=Dv,
                                      interpret=True)

    jaxpr = str(jax.make_jaxpr(jax.vmap(one))(q, pool, lengths))
    assert jaxpr.count("pallas_call") == 1 and "while" not in jaxpr
    assert "name=latent_decode" in jaxpr
    got = jax.vmap(one)(q, pool, lengths)
    for lane in range(5):
        np.testing.assert_allclose(
            got[lane], one(q[lane], pool[lane], lengths[lane]), atol=1e-6)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_append_of_a_latent_row_is_the_xla_update_bitwise(dtype):
    """`flash_cache_append` with the one leaf: positions at a tile's
    ends, the cache's last and one past it (clamped, as
    `dynamic_update_slice` clamps)."""
    pos = [0, 5, 15, 16, W - 1, W]
    r = np.random.RandomState(4)
    pool = jnp.asarray(r.randn(len(pos), W, 1, 128), dtype)
    new = jnp.asarray(r.randn(len(pos), 1, 1, 128), dtype)
    got, none = flash_cache_append(pool, None, new, None,
                                   jnp.asarray(pos, jnp.int32),
                                   interpret=True)
    assert none is None
    want = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
        c, n, (i, jnp.int32(0), jnp.int32(0))))(
        pool, new, jnp.asarray(pos, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_layer_step_through_the_kernel_equals_the_walk(state):
    """The whole sublayer's S = 1 step with the kernel forced
    (interpret mode), vmapped over lanes of ragged fill - a lane at 0
    among them - against the lax walk: outputs, rows and indices."""
    params, empty = state
    fills = [0, 1, 16, 17, W - 1, 37]
    r = np.random.RandomState(5)
    rows = jnp.asarray(r.randn(len(fills), 1, W, SPEC.stored), jnp.float32)
    rows = rows.at[..., SPEC.row:].set(0)
    cache = {"cached_latent": rows,
             "cache_index": jnp.asarray(fills, jnp.int32)}
    x = jnp.asarray(r.randn(len(fills), 1, 1, 64), jnp.float32)

    def step(impl):
        def one(sub, x):
            return layer(impl).apply({"params": params, "cache": sub}, x,
                                     mutable=["cache"])
        return jax.jit(jax.vmap(one))(cache, x)

    want, cw = step("lax")
    got, cg = step("pallas")
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(cg["cache"]["cache_index"],
                                  np.asarray(fills) + 1)
    for a, b in zip(jax.tree.leaves(cg), jax.tree.leaves(cw)):
        np.testing.assert_array_equal(a, b)


# ---- the expert layer: identity experts, weights, the bias ----------------------------
def moe(held=None, **kw):
    kw = {"num_experts": 32, "zero_experts": 16, "hidden": 24, "k": 6,
          "router": "softmax", "router_bias": True, "normalize": False,
          "scale": 6.0, "dtype": jnp.float32, **kw}
    return HeldExpertsMoE(held=held, **kw)


MOE_ARCH = dict(ARCH, hidden_size=48, num_experts=32, zero_experts=16,
                experts_held=[0, 32], experts_per_token=6,
                expert_hidden=24)


@pytest.fixture(scope="module")
def moe_state():
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, 48), jnp.float32)
    p = unbox(moe().init(jax.random.PRNGKey(6), x))["params"]
    assert p["router"].shape == (48, 48) and p["router_bias"].shape == (48,)
    assert p["w_gate"].shape == (32, 48, 24)    # identity: no weights
    return x, dict(p, router=p["router"] * 3.0)


def test_the_shares_of_a_cut_router_add_up_to_the_uncut_layer(moe_state):
    """THE SHARE TEST. Over all 4 shares of a router of 32 real + 16
    identity experts (8 real experts a chip) the routed parts, plus the
    identity part counted ONCE (it is the token's own chip's), are the
    uncut reference's layer; and each share is the reference's too."""
    x, p = moe_state
    uncut = A.moe(MOE_ARCH, p, x[0])
    whole, mut = moe().apply({"params": p}, x, mutable=["moe_stats"])
    np.testing.assert_allclose(whole[0], uncut, atol=3e-5)
    identity = moe().apply(         # no real expert gives anything
        {"params": dict(p, **{k: jnp.zeros_like(p[k]) for k in
                              ("w_gate", "w_up", "w_down")})}, x)
    chosen, w = A.route(MOE_ARCH, p, x[0])
    want = jnp.where(chosen >= 32, w, 0.0).sum(-1)[:, None] * x[0]
    np.testing.assert_allclose(identity[0], want, atol=3e-5)
    total = identity
    for chip in range(4):
        part = dict(p, **{k: p[k][8 * chip:8 * chip + 8] for k in
                          ("w_gate", "w_up", "w_down")})
        share = moe((8 * chip, 8)).apply({"params": part}, x)
        np.testing.assert_allclose(
            share[0], A.moe(MOE_ARCH, part, x[0], held=(8 * chip, 8)),
            atol=3e-5)
        total = total + share - identity
    np.testing.assert_allclose(total[0], uncut, atol=5e-5)
    assert float(jnp.abs(uncut - identity[0]).max()) > 1e-2
    assert float(jnp.abs(identity).max()) > 1e-2
    # the counters: pairs on identity experts, and all the pairs chosen
    zero, all_pairs = map(int, mut["moe_stats"]["routed"])
    assert all_pairs == 40 * 6
    assert zero == int((chosen >= 32).sum()) and 0 < zero < all_pairs
    assert int(mut["moe_stats"]["pairs"].sum()) == all_pairs - zero


def test_weights_are_not_normalised_and_the_bias_only_chooses(moe_state):
    x, p = moe_state
    chosen, w = A.route(MOE_ARCH, p, x[0])
    assert float(w.sum(-1).max()) < 6.0                     # not 6 x 1
    assert float(jnp.abs(w.sum(-1) - 6.0).mean()) > 0.3
    probs = jax.nn.softmax(x[0] @ p["router"], -1)
    np.testing.assert_allclose(
        w, 6.0 * jnp.take_along_axis(probs, chosen, -1), rtol=1e-5)
    # a bias that lifts expert 5 and identity expert 40 into every choice
    bias = jnp.zeros(48).at[jnp.asarray([5, 40])].set(10.0)
    pb = dict(p, router_bias=bias)
    cb, wb = A.route(MOE_ARCH, pb, x[0])
    assert bool((cb == 5).any(-1).all()) and bool((cb == 40).any(-1).all())
    np.testing.assert_allclose(      # ... and weighs nothing
        wb, 6.0 * jnp.take_along_axis(probs, cb, -1), rtol=1e-5)
    y = moe().apply({"params": pb}, x)
    np.testing.assert_allclose(y[0], A.moe(MOE_ARCH, pb, x[0]), atol=3e-5)
    assert float(jnp.abs(y - moe().apply({"params": p}, x)).max()) > 1e-3


@pytest.mark.parametrize("router", ["sigmoid", "softmax"])
def test_the_older_routers_are_the_expression_they_were(router):
    """`zero_experts`, `normalize` and `router_bias` at their defaults
    leave solar's (sigmoid + bias) and laguna's (softmax, no bias)
    layers the expression they were, bitwise, on the same parameter
    tree; stated explicitly they trace to the same program."""
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 24, 48), jnp.float32)
    kw = dict(num_experts=16, hidden=32, k=4, held=(4, 8), router=router,
              scale=2.5, dtype=jnp.float32)
    old = HeldExpertsMoE(**kw)
    p = unbox(old.init(jax.random.PRNGKey(10), x))["params"]
    assert sorted(p) == sorted(
        ["router", "w_gate", "w_up", "w_down"]
        + (["router_bias"] if router == "sigmoid" else []))
    assert p["router"].shape == (48, 16)
    if router == "sigmoid":
        p = dict(p, router_bias=jnp.linspace(-0.2, 0.2, 16))
    got, mut = old.apply({"params": p}, x, mutable=["moe_stats"])
    assert set(mut["moe_stats"]) == {"pairs"}

    xt = x.reshape(-1, 48)
    logits = jnp.matmul(xt, p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    if router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + p["router_bias"], 4)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, 4)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / weight.sum(-1, keepdims=True) * 2.5
    local = chosen - 4
    key = jnp.where((local >= 0) & (local < 8), local, 8)
    want = grouped_experts(xt, key, weight, p["w_gate"], p["w_up"],
                           p["w_down"]).reshape(x.shape)
    np.testing.assert_array_equal(got, want)
    explicit = HeldExpertsMoE(zero_experts=0, normalize=True,
                              router_bias=None, **kw)
    assert str(jax.make_jaxpr(lambda p, x: explicit.apply(
        {"params": p}, x))(p, x)) == str(jax.make_jaxpr(
            lambda p, x: old.apply({"params": p}, x))(p, x))


# ---- the rotary rule and the softmax factor of the DeepSeek family ---------------
# A.X-K1's rule at a toy width: YaRN over the 4 frequencies of an 8-wide
# rope part (factor 32 from an original length of 64: the ramp runs over
# j = 0 .. 2), cos and sin times 1.0, the scale times mscale^2.
YARN = dict(factor=32.0, original=64, beta_fast=32.0, beta_slow=1.0)
FACTOR = (0.1 * math.log(YARN["factor"]) + 1.0) ** 2
SPEC_Y = LatentSpec(
    q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16,
    rope=RopeSpec(theta=1e4, yarn_factor=YARN["factor"],
                  yarn_original_len=YARN["original"],
                  yarn_beta_fast=YARN["beta_fast"],
                  yarn_beta_slow=YARN["beta_slow"], scale=1.0),
    softmax_factor=FACTOR)


def formula(p, x, *, yarn=True, factor=FACTOR, eps=1e-5, theta=1e4):
    """ISSUE 41's equations in NumPy float64, a position at a time: the
    whole sublayer on x [S, d] - [S, d]."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    x, (H, n, r, kvr) = np.asarray(x, np.float64), (4, 16, 8, 16)
    rms = lambda v, g: v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * g
    j = np.arange(r // 2)
    inv = theta ** (-2.0 * j / r)
    if yarn:
        at = lambda b: r * math.log(YARN["original"] / (2 * math.pi * b)) / (
            2 * math.log(theta))
        lo, hi = max(math.floor(at(32.0)), 0), min(math.ceil(at(1.0)), r - 1)
        ramp = np.clip((j - lo) / (hi - lo), 0, 1)
        inv = inv * ((1 - ramp) + ramp / YARN["factor"])

    def rot(v, t):          # v [..., r] at position t, pairs (2j, 2j + 1)
        c, s = np.cos(t * inv), np.sin(t * inv)
        a, b = v[..., 0::2], v[..., 1::2]
        return np.stack([a * c - b * s, a * s + b * c], -1).reshape(v.shape)

    q = (rms(x @ p["q_a"]["kernel"], p["q_a_norm"]["scale"])
         @ p["q_b"]["kernel"]).reshape(-1, H, n + r)
    kv = x @ p["kv_a"]["kernel"]
    c = rms(kv[:, :kvr], p["kv_a_norm"]["scale"])
    out = []
    for t in range(len(x)):
        k_rope = np.stack([rot(kv[i, kvr:], i) for i in range(t + 1)])
        k_nope = np.einsum("jr,rhn->jhn", c[:t + 1], p["k_up"])
        v = np.einsum("jr,rhv->jhv", c[:t + 1], p["v_up"])
        score = (np.einsum("hn,jhn->hj", q[t, :, :n], k_nope)
                 + rot(q[t, :, n:], t) @ k_rope.T) * factor / math.sqrt(n + r)
        w = np.exp(score - score.max(-1, keepdims=True))
        o = np.einsum("hj,jhv->hv", w / w.sum(-1, keepdims=True), v)
        out.append(o.reshape(-1) @ p["out"]["kernel"])
    return np.stack(out)


def layer_y(impl=None, spec=SPEC_Y):
    return layer(impl).clone(spec=spec)


@pytest.mark.parametrize("form", ["expanded", "absorbed", "kernel"])
def test_every_form_turns_the_shared_key_by_the_layer_s_rule(state, form):
    """The rule and the factor reach all three forms: the full forward
    (expanded), chunks and steps through the lax walk (absorbed), and
    S = 1 steps through the ragged kernel in interpret mode (the
    pre-scaled query) - each is the formula above."""
    params, empty = state
    x = xs(27, seed=7)
    want = formula(params, x[0])
    assert SPEC_Y.softmax_scale == pytest.approx(1.81326 / math.sqrt(24),
                                                 rel=1e-5)
    if form == "expanded":
        got = layer_y().clone(decode=False).apply({"params": params}, x)
    else:
        def run(cache, rows, impl=None):
            y, mut = layer_y(impl).apply(
                {"params": params, "cache": cache}, rows,
                mutable=["cache"])
            return y, mut["cache"]

        out, cache = [], empty
        for lo, hi in ((0, 16), (16, 24)):
            y, cache = run(cache, x[:, lo:hi])
            out.append(y)
        for t in range(24, 27):
            y, cache = run(cache, x[:, t:t + 1],
                           "pallas" if form == "kernel" else "lax")
            out.append(y)
        got = jnp.concatenate(out, 1)
    np.testing.assert_allclose(got[0], want, atol=3e-5)


@pytest.mark.parametrize("control,kw", [
    ("scale factor 1.0", dict(factor=1.0)),
    ("plain frequencies", dict(yarn=False)),
    ("neither", dict(yarn=False, factor=1.0))])
def test_the_rule_and_the_factor_move_the_output(state, control, kw):
    """The comparison sees both mechanisms: the formula without either
    is NOT what the layer computes - and the layer without them
    (LongCat's spec: plain rotation, factor 1) is that formula."""
    params, _ = state
    x = xs(27, seed=7)
    got = np.asarray(layer_y().clone(decode=False).apply(
        {"params": params}, x))[0]
    assert np.abs(got - formula(params, x[0], **kw)).max() > 1e-3, control
    plain = dataclasses.replace(SPEC_Y, rope=None, softmax_factor=1.0)
    np.testing.assert_allclose(
        np.asarray(layer_y(spec=plain).clone(decode=False).apply(
            {"params": params}, x))[0],
        formula(params, x[0], yarn=False, factor=1.0), atol=3e-5)


def test_yarn_frequencies_are_the_published_ramp_at_the_real_width():
    """ISSUE 41's three numbers at A.X-K1's widths: the ramp over
    j = 10 .. 23 of 32 frequencies, cos / sin x 1.0, scale x 1.81326;
    LongCat's spec keeps the rule it always had."""
    rope = RopeSpec(theta=1e4, yarn_factor=32.0, yarn_original_len=4096)
    assert rope.yarn_ramp(64) == (10, 23)
    inv = rope.inv_freq(64)
    plain = 1e4 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], plain[23:] / 32, rtol=1e-12)
    assert plain[16] / 32 < inv[16] < plain[16]
    pub = LatentSpec(q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
                     v_dim=128, rope=rope,
                     softmax_factor=(0.1 * math.log(32) + 1) ** 2)
    assert pub.softmax_scale * math.sqrt(192) == pytest.approx(
        1.81326, abs=1e-5)
    assert RopeSpec(theta=1e7).rotation(64) == {"theta": 1e7}
    assert LatentSpec(q_rank=8, kv_rank=8, nope_dim=8, rope_dim=8,
                      v_dim=8).softmax_scale == 16 ** -0.5
