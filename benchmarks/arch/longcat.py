"""Architecture module `longcat`: everything the benchmark knows of the
LongCat-Flash layer (two latent-attention sublayers over a head-less
cache, two dense SwiGLU FFNs, and a shortcut-connected expert layer
whose router is wider than its experts: ids past the real experts are
identity experts that cost nothing), for kind `serve_arch`.

A configuration names its module (`"arch_module": "longcat"`) and the
kind takes from it, and from nowhere else:

    program_model(arch, max_len, attn_impl)   the program's model
    layout / make_params / check_layout / count   weights from --seed
    served_logits(arch, params, prompt, served, quant)   the plain reference
    reference_routing(arch, params, tokens)    the reference's chosen ids
    tick_least_seconds(...), latent_decode_least_seconds(...) and the
    byte and flop counts behind them

THE PLAIN REFERENCE is the part from `embed` down: the forward pass in
`jax.numpy`, float32, every product at `Precision.HIGHEST`, the
EXPANDED equations (keys and values made from the latent, a head at a
time as any softmax attention), a loop over the experts held, no cache,
no kernels, no batching; the queries of a sublayer go through attention
a block at a time (`ATTN_BLOCK` rows against every key they may see),
and each sublayer is a jitted piece of its own that casts a matrix at
a time, so that the bf16 weights of the whole cut and one piece's
float32 fit beside each other on the chip. It imports nothing of the
program. Given the chip's share (the experts held, the sliced
vocabulary) it leaves out what the absent experts would add, as the
program does. `quant` is the control, as in `harness/reference.py`.

One layer, on input x (d = hidden; RMSNorm eps everywhere; no biases;
a final RMSNorm, an untied head)::

    x1 = x  + MLA_0(norm_a0(x))
    h  = norm_m0(x1)
    s  = MoE(h)                   # the shortcut: read only by the last line
    x2 = x1 + FFN_0(h)            # SwiGLU of width dense_hidden
    x3 = x2 + MLA_1(norm_a1(x2))
    x4 = x3 + FFN_1(norm_m1(x3))
    y  = x4 + s

MLA (H heads; ranks q_r, kv_r; head parts nope n, rope r; values v), on
its normed input u at position t::

    c_q = RMSNorm(W_qa u);   q = a_q (W_qb c_q)  in [H, n + r]
    [c_kv ; k_r] = W_kva u;  c = a_kv RMSNorm(c_kv)  in R^kv_r
    a_q = sqrt(d / q_r), a_kv = sqrt(d / kv_r)   (mla_scale_*_lora)
    k_rope = RoPE_t(k_r) (one head, shared); q_rope = RoPE_t(q[:, n:])
    RoPE on interleaved pairs (2j, 2j + 1), inv_freq_j = theta^(-2j / r)
    [k_nope_h ; v_h] = W_kvb,h c       (kept as W_UK [kv_r, H, n] and
                                        W_UV [kv_r, H, v])
    score_h(t, j) = (q_nope_h . k_nope_h(j) + q_rope_h . k_rope(j))
                    / sqrt(n + r),  causal;   o_h = softmax_j(.) v_h(j)
    out = W_o concat_h(o_h)

MoE (router outputs N + Z: N real experts, Z identity experts; k a
token; scale)::

    p = softmax(W_r h) over all N + Z, float32
    chosen = the k largest of p + b          (b: choice only)
    w_e = scale * p_e for e in chosen        (NOT normalised)
    s = sum_{e chosen, e < N, e HELD HERE} w_e SwiGLU_e(h)
        + (sum_{e chosen, e >= N} w_e) h
"""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp

from benchmarks.harness import cells
from benchmarks.harness.reference import HIGHEST, _mm
from benchmarks.harness.weights import seed_key

# unchanged helpers, not copied a third time (ROADMAP W0 folds them)
_laguna = cells.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "laguna.py"),
    "benchmarks_arch_laguna")
_is_spec, _frozen, _pad_to = (_laguna._is_spec, _laguna._frozen,
                              _laguna._pad_to)
_rms, _swiglu = _laguna._rms, _laguna._swiglu

ATTN_BLOCK = 512        # query rows the reference attends at a time
SUBLAYERS = 2           # latent-attention sublayers (and dense FFNs) a layer


def scales(arch):
    """(a_q, a_kv): LongCat's two factors, 1 where the flag is off."""
    d = arch["hidden_size"]
    return (math.sqrt(d / arch["q_lora_rank"])
            if arch["mla_scale_q_lora"] else 1.0,
            math.sqrt(d / arch["kv_lora_rank"])
            if arch["mla_scale_kv_lora"] else 1.0)


# ---- the program's model ----------------------------------------------
def program_model(arch, *, max_len, attn_impl=None, dtype=None):
    """`TransformerLM` for this `arch`: the one place that knows its
    field names for a model of LongCat layers."""
    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.parallel.latent_attention import LatentSpec

    a_q, a_kv = scales(arch)
    kw = dict(
        vocab_size=arch["vocab_size"], num_layers=arch["num_layers"],
        hidden_size=arch["hidden_size"], num_heads=arch["num_heads"],
        head_dim=arch["v_head_dim"], pos_emb="rope",
        rope_theta=arch["rope_theta"], max_len=int(max_len),
        norm="rmsnorm", ln_eps=arch["norm_eps"],
        tied_head=arch["tied_head"],
        layer_kinds=("mla",) * arch["num_layers"],
        latent=LatentSpec(
            q_rank=arch["q_lora_rank"], kv_rank=arch["kv_lora_rank"],
            nope_dim=arch["qk_nope_head_dim"],
            rope_dim=arch["qk_rope_head_dim"], v_dim=arch["v_head_dim"],
            q_scale=a_q, kv_scale=a_kv),
        mlp_impl="swiglu", mlp_hidden=arch["dense_hidden"],
        moe_every=1, moe_impl="dropless", moe_shortcut=True,
        num_experts=arch["num_experts"], moe_k=arch["experts_per_token"],
        moe_hidden=arch["expert_hidden"],
        moe_held=tuple(arch["experts_held"]),
        moe_router=arch["router"], moe_router_bias=arch["router_bias"],
        moe_normalize=arch["norm_topk"], moe_scale=arch["routed_scale"],
        moe_zero_experts=arch["zero_experts"],
        dtype=jnp.dtype(dtype or arch["compute_dtype"]))
    if attn_impl:
        kw["attn_impl"] = attn_impl
    return TransformerLM(**kw)


# ---- weights from the seed --------------------------------------------
def layout(arch, max_len=None):
    """Nested dict of (shape, kind), the parameter tree the program's
    model declares. Kinds: 'matrix' normal(0, 0.02) kept in the matrix
    dtype; 'scale' 1 + normal(0, 0.02); 'zero' float32 zeros (the
    router's selection bias: b = 0 in the seeded weights)."""
    del max_len                         # no position table
    d, V, H = arch["hidden_size"], arch["vocab_size"], arch["num_heads"]
    qr, kvr = arch["q_lora_rank"], arch["kv_lora_rank"]
    n, r, v = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
               arch["v_head_dim"])
    outputs = arch["num_experts"] + arch["zero_experts"]
    (_, E), m = arch["experts_held"], arch["expert_hidden"]

    def dense(i, o):
        return {"kernel": ((i, o), "matrix")}

    def norm(width=d):
        return {"scale": ((width,), "scale")}

    def swiglu(width):
        return {"gate": dense(d, width), "up": dense(d, width),
                "down": dense(width, d)}

    mla = {"q_a": dense(d, qr), "q_a_norm": norm(qr),
           "q_b": dense(qr, H * (n + r)),
           "kv_a": dense(d, kvr + r), "kv_a_norm": norm(kvr),
           "k_up": ((kvr, H, n), "matrix"), "v_up": ((kvr, H, v), "matrix"),
           "out": dense(H * v, d)}
    moe = {"router": ((d, outputs), "matrix"),
           "w_gate": ((E, d, m), "matrix"), "w_up": ((E, d, m), "matrix"),
           "w_down": ((E, m, d), "matrix")}
    if arch["router_bias"]:
        moe["router_bias"] = ((outputs,), "zero")
    tree = {"embed": ((V, d), "matrix"), "lm_head": ((V, d), "matrix"),
            "ln_f": norm()}
    if arch["tied_head"]:
        del tree["lm_head"]
    for i in range(arch["num_layers"]):
        blk = {"moe": moe}
        for j in range(SUBLAYERS):
            blk.update({f"mla_{j}": mla, f"mlp_{j}":
                        swiglu(arch["dense_hidden"]),
                        f"ln_attn_{j}": norm(), f"ln_mlp_{j}": norm()})
        tree[f"block_{i}"] = blk
    return tree


def _draw(key, shape, kind, matrix_dtype):
    if kind == "zero":
        return jnp.zeros(shape, jnp.float32)
    return _laguna._draw(key, shape, kind, matrix_dtype)


@functools.lru_cache(maxsize=None)
def _maker(arch_json, matrix_dtype):
    spec = layout(json.loads(arch_json))
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_spec)
    matrix_dtype = jnp.dtype(matrix_dtype)

    def make(key):
        # one draw a leaf: the expert tensors are too large to stack
        return jax.tree.unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), shape, kind, matrix_dtype)
            for i, (shape, kind) in enumerate(leaves)])

    return jax.jit(make)


def make_params(arch, max_len, seed, matrix_dtype):
    """The whole tree on the default device, in one jitted call, from
    `--seed` alone (the same key rule as `harness/weights.py`)."""
    del max_len
    return _maker(_frozen(arch), str(matrix_dtype))(seed_key(seed))


def check_layout(arch, max_len, model):
    """Names and shapes of `layout` against what the program's model
    declares (shapes only: nothing is computed)."""
    from horovod_tpu.parallel.tensor import unbox

    want = unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"])
    want = {jax.tree_util.keystr(k): tuple(v.shape) for k, v in
            jax.tree_util.tree_leaves_with_path(want)}
    have = {jax.tree_util.keystr(k): v[0] for k, v in
            jax.tree_util.tree_leaves_with_path(
                layout(arch, max_len), is_leaf=_is_spec)}
    if want != have:
        diff = sorted(set(want.items()) ^ set(have.items()))
        raise SystemExit(
            f"the benchmark's weight layout and the program's "
            f"parameter tree differ: {diff[:8]}")


def count(arch, max_len=None):
    return sum(math.prod(s) for s, _ in jax.tree.leaves(
        layout(arch, max_len), is_leaf=_is_spec))


# ---- the plain reference ------------------------------------------------
def rotate(arch, x, positions):
    """x [S, heads, r] at `positions` [S]: pairs (2j, 2j + 1) turned by
    position x theta^(-2j / r)."""
    r = x.shape[-1]
    inv = arch["rope_theta"] ** (
        -jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _attend(q, k, v, qpos, kpos):
    """q [Sq, H, Dk] at qpos against k [Sk, H, Dk], v [Sk, H, Dv] at
    kpos: causal softmax, already scaled."""
    s = jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST)
    s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
    return jnp.einsum("hst,thd->shd", jax.nn.softmax(s, axis=-1), v,
                      precision=HIGHEST)


def mla(arch, p, u, quant=None):
    """u [S, d] -> [S, d]: one latent-attention sublayer, expanded."""
    S, H = u.shape[0], arch["num_heads"]
    kvr, n, r, v = (arch["kv_lora_rank"], arch["qk_nope_head_dim"],
                    arch["qk_rope_head_dim"], arch["v_head_dim"])
    eps, (a_q, a_kv) = arch["norm_eps"], scales(arch)
    pos = jnp.arange(S)
    cq = _rms(_mm(u, p["q_a"]["kernel"], quant), p["q_a_norm"]["scale"],
              eps)
    q = a_q * _mm(cq, p["q_b"]["kernel"], quant).reshape(S, H, n + r)
    kv = _mm(u, p["kv_a"]["kernel"], quant)
    c = a_kv * _rms(kv[:, :kvr], p["kv_a_norm"]["scale"], eps)
    k_rope = rotate(arch, kv[:, None, kvr:], pos)            # [S, 1, r]
    q = jnp.concatenate([q[..., :n], rotate(arch, q[..., n:], pos)], -1)
    k = jnp.concatenate(
        [_mm(c, p["k_up"].reshape(kvr, H * n), quant).reshape(S, H, n),
         jnp.broadcast_to(k_rope, (S, H, r))], -1)
    val = _mm(c, p["v_up"].reshape(kvr, H * v), quant).reshape(S, H, v)
    q = q * (n + r) ** -0.5
    blk = math.gcd(S, ATTN_BLOCK)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, 0)
        return _attend(qb, k, val, start + jnp.arange(blk), pos)

    o = jax.lax.map(rows, jnp.arange(0, S, blk)).reshape(S, H * v)
    return _mm(o, p["out"]["kernel"], quant)


def route(arch, p, x, quant=None):
    """(chosen [S, k] ids over ALL router outputs, weights [S, k])."""
    if arch["router"] != "softmax":
        raise ValueError(arch["router"])
    s = jax.nn.softmax(_mm(x, p["router"], quant), axis=-1)
    pick = s + p["router_bias"] if arch["router_bias"] else s
    _, chosen = jax.lax.top_k(pick, arch["experts_per_token"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if arch["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return chosen, arch["routed_scale"] * w


def moe(arch, p, x, quant=None, held=None, identity=True):
    """x [S, d] -> [S, d]: the identity experts' part (the layer's own
    input times the sum of the token's weights on ids >= num_experts;
    ``identity=False`` leaves it out - the share test counts it once)
    plus the part that the experts `held` = (first, count) give: a
    loop over those experts, each applied to every token and weighted
    by the token's weight for it (0 where it was not chosen).
    `p["w_*"]` hold exactly those experts."""
    first, n = held or arch["experts_held"]
    chosen, w = route(arch, p, x, quant)
    zero = jnp.where(chosen >= arch["num_experts"], w, 0.0).sum(-1)
    y = zero[:, None] * x if identity else jnp.zeros_like(x)

    def one(y, e):
        gate, up, down, idx = e
        we = jnp.where(chosen == first + idx, w, 0.0).sum(-1)
        ye = _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant),
                 down, quant)
        return y + we[:, None] * ye, None

    y, _ = jax.lax.scan(one, y, (p["w_gate"], p["w_up"], p["w_down"],
                                 jnp.arange(n)))
    return y


def _norm(arch, p, name, x):
    return _rms(x, p[name]["scale"], arch["norm_eps"])


# The layer as four pieces, each small enough to be cast to float32 at
# once beside the whole cut's bf16 weights: (x, shortcut) -> (x, shortcut)
def piece(arch, step, p, x, s, quant=None):
    if step == "mla_0":
        return x + mla(arch, p["mla_0"], _norm(arch, p, "ln_attn_0", x),
                       quant), s
    if step == "moe":               # from x1, before FFN_0 moves x on
        return x, moe(arch, p["moe"], _norm(arch, p, "ln_mlp_0", x),
                      quant)
    if step == "mlp_0":
        return x + _swiglu(_norm(arch, p, "ln_mlp_0", x), p["mlp_0"],
                           quant), s
    if step == "mla_1":
        return x + mla(arch, p["mla_1"], _norm(arch, p, "ln_attn_1", x),
                       quant), s
    if step == "mlp_1":             # and the shortcut joins
        return x + _swiglu(_norm(arch, p, "ln_mlp_1", x), p["mlp_1"],
                           quant) + s, s
    raise ValueError(step)


STEPS = ("mla_0", "moe", "mlp_0", "mla_1", "mlp_1")


def block(arch, p, x, quant=None):
    s = jnp.zeros_like(x)
    for step in STEPS:
        x, s = piece(arch, step, p, x, s, quant)
    return x


def embed(arch, params, tokens):
    return jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)


def head(arch, params, hidden, quant=None):
    h = _rms(hidden, params["ln_f"]["scale"], arch["norm_eps"])
    table = params["embed" if arch["tied_head"] else "lm_head"]
    return _mm(h, table.astype(jnp.float32).T, quant)


def logits(arch, params, tokens, quant=None):
    """tokens [S] -> [S, V]: the whole forward pass."""
    x = embed(arch, params, tokens)
    for i in range(arch["num_layers"]):
        x = block(arch, params[f"block_{i}"], x, quant)
    return head(arch, params, x, quant)


def routing(arch, params, tokens):
    """The reference's chosen ids: [layers, S, k], sorted per token."""
    x = embed(arch, params, jnp.asarray(tokens))
    out = []
    for i in range(arch["num_layers"]):
        p = params[f"block_{i}"]
        x, s = piece(arch, "mla_0", p, x, jnp.zeros_like(x))
        out.append(jnp.sort(route(
            arch, p["moe"], _norm(arch, p, "ln_mlp_0", x))[0], axis=-1))
        for step in STEPS[1:]:
            x, s = piece(arch, step, p, x, s)
    return jnp.stack(out)


@functools.lru_cache(maxsize=None)
def _jitted(what, arch_json, quant):
    arch = json.loads(arch_json)
    if what == "embed":
        return jax.jit(functools.partial(embed, arch))
    if what in STEPS:
        return jax.jit(functools.partial(piece, arch, what, quant=quant))
    if what == "head_rows":
        def rows_head(params, hid, start, n_rows):
            rows = jax.lax.dynamic_slice_in_dim(hid, start, n_rows, 0)
            return head(arch, params, rows, quant)
        return jax.jit(rows_head, static_argnames=("n_rows",))
    if what == "routing":
        return jax.jit(functools.partial(routing, arch))
    raise ValueError(what)


def _fn(what, arch, quant=None):
    return _jitted(what, _frozen(arch), quant)


def served_logits(arch, params, prompt, served, quant=None,
                  seq_block=ATTN_BLOCK, row_block=256):
    """Logits [len(served), V] of the reference at each position whose
    next token the system served: one full forward over prompt ++
    served (teacher-forced; everything is causal, so the padding after
    the end reaches nothing), piece by piece, the weights upcast a
    piece at a time. Lengths are padded to blocks so that a few shapes
    compile."""
    import numpy as np
    P, n = len(prompt), len(served)
    n_rows = _pad_to(n, row_block)
    seq = np.zeros(_pad_to(P - 1 + n_rows, seq_block), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = served[:n - 1]
    x = _fn("embed", arch)(params, jnp.asarray(seq))
    for i in range(arch["num_layers"]):
        s = jnp.zeros_like(x)
        for step in STEPS:
            x, s = _fn(step, arch, quant)(params[f"block_{i}"], x, s)
    return _fn("head_rows", arch, quant)(params, x, P - 1,
                                         n_rows=n_rows)[:n]


def reference_routing(arch, params, tokens, seq_block=ATTN_BLOCK):
    """`routing` over tokens padded to a block: [layers, len, k] - what
    `serve_arch.routing_flips` compares the program's `chosen` with."""
    import numpy as np
    seq = np.zeros(_pad_to(len(tokens), seq_block), np.int32)
    seq[:len(tokens)] = tokens
    return np.asarray(_fn("routing", arch)(params, seq))[:, :len(tokens)]


# ---- required bytes and operations of one decode tick --------------------
def sublayers(arch):
    return SUBLAYERS * arch["num_layers"]


def expert_params(arch):
    return 3 * arch["hidden_size"] * arch["expert_hidden"]


def other_matmul_params(arch):
    """Parameters outside the routed experts that a decoded token
    multiplies: both latent sublayers (in the absorbed step W_UK and
    W_UV are multiplied once a row like any other matrix), both dense
    FFNs, the router, and the head (the embedding lookup multiplies
    nothing; the identity experts have no parameters)."""
    tree = layout(arch)
    total = math.prod(tree["lm_head" if not arch["tied_head"]
                           else "embed"][0])
    for i in range(arch["num_layers"]):
        blk = dict(tree[f"block_{i}"])
        blk["moe"] = {"router": blk["moe"]["router"]}
        total += sum(math.prod(s) for s, kind in jax.tree.leaves(
            blk, is_leaf=_is_spec) if kind == "matrix")
    return total


def latent_row(arch):
    """Numbers a cached position holds in one sublayer."""
    return arch["kv_lora_rank"] + arch["qk_rope_head_dim"]


def latent_flops_per_position(arch):
    """Absorbed scores and weighted sum of one cached position in one
    sublayer: H heads x (a row for the score + its latent part for the
    sum), 2 flops a multiply-add."""
    return 2 * arch["num_heads"] * (latent_row(arch)
                                    + arch["kv_lora_rank"])


def tick_bytes(arch, lanes_decoding, context_sum, experts_hit,
               weight_bytes=2, cache_bytes=2):
    """Bytes one tick must move: the weights of the experts that got a
    pair (`experts_hit`, summed over layers), every other weight once,
    the latent rows of the cached positions (`context_sum`) in every
    sublayer ONCE (keys and values are the same bytes), and one row a
    lane and sublayer written."""
    return (experts_hit * expert_params(arch) * weight_bytes
            + other_matmul_params(arch) * weight_bytes
            + (context_sum + lanes_decoding) * sublayers(arch)
            * latent_row(arch) * cache_bytes)


def tick_flops(arch, lanes_decoding, context_sum, pairs):
    """Flops one tick must do: 2 per parameter a row multiplies (the
    other weights per decoding lane, an expert per held pair), and the
    absorbed attention over the cached positions in every sublayer.
    Identity experts: one multiply-add of d a pair, not counted."""
    return (2 * other_matmul_params(arch) * lanes_decoding
            + 2 * expert_params(arch) * pairs
            + sublayers(arch) * latent_flops_per_position(arch)
            * context_sum)


def tick_least_seconds(arch, peaks, *, lanes_decoding, context_sum,
                       experts_hit, pairs):
    """(seconds, "bytes" | "flops"): the least time the chip could
    take for what the tick was asked to do."""
    t_b = (tick_bytes(arch, lanes_decoding, context_sum, experts_hit)
           / peaks["hbm_bytes_per_s"])
    t_f = (tick_flops(arch, lanes_decoding, context_sum, pairs)
           / peaks["bf16_flops_per_s"])
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def latent_decode_least_seconds(arch, peaks, *, lanes_decoding,
                                context_sum, cache_bytes=2):
    """(seconds, "bytes" | "flops") of ONE sublayer's decode-kernel
    call: the decoding lanes' cached rows read once (their own new row
    among them), a query and a result a lane and head, against the
    absorbed flops over the same positions. Least bytes only: what
    the lanes that do not decode make the kernel read is not asked."""
    H, row = arch["num_heads"], latent_row(arch)
    positions = context_sum + lanes_decoding
    t_b = ((positions * row + lanes_decoding * H
            * (row + arch["kv_lora_rank"])) * cache_bytes
           / peaks["hbm_bytes_per_s"])
    t_f = (latent_flops_per_position(arch) * positions
           / peaks["bf16_flops_per_s"])
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
