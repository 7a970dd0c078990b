"""Architecture module `axk1`: everything the benchmark knows of the
A.X-K1 layer (the DeepSeek-V3 family's block: latent attention whose
rope part turns at YaRN's frequencies, then a dense SwiGLU FFN in the
leading layer and, in every other, sigmoid-routed experts chosen from a
limited number of groups beside one shared expert), for kind
`serve_arch`.

A configuration names its module (`"arch_module": "axk1"`) and the kind
takes from it, and from nowhere else:

    program_model(arch, max_len, attn_impl)   the program's model
    layout / make_params / check_layout / count   weights from --seed
    served_logits(arch, params, prompt, served, quant)   the plain reference
    expert_routing(arch, params, tokens)    the reference's chosen ids
    tick_least_seconds(...), latent_decode_least_seconds(...) and the
    byte and flop counts behind them

THE PLAIN REFERENCE is the part from `embed` down: the forward pass in
`jax.numpy`, float32, every product at `Precision.HIGHEST`, the
EXPANDED equations (keys and values made from the latent, a head at a
time as any softmax attention), a loop over the experts held, no cache,
no kernels, no batching; the queries go through attention a block at a
time (`ATTN_BLOCK` rows against every key they may see), and each
sublayer is a jitted piece of its own that casts a matrix at a time, so
that the bf16 weights of the whole cut and one piece's float32 fit
beside each other on the chip. It imports nothing of the program. Given
the chip's share (the experts held, the sliced vocabulary) it scores
all the router's outputs, chooses over all the groups, and leaves out
what the absent experts would add, as the program does. `quant` is the
control: "int8" / "fp8" as in `harness/reference.py`, and the controls
that only this model can fail - `CONTROLS_OF_THE_MODEL`.

One layer, on input x (d = hidden; RMSNorm eps everywhere; no biases; a
final RMSNorm, an untied head)::

    x1 = x + MLA(norm_a(x));   h = norm_m(x1)
    y  = x1 + SwiGLU_dense(h)                      in `dense_layers`
    y  = x1 + Routed(h) + SwiGLU_shared(h)         in every other

MLA (H heads; ranks q_r, kv_r; head parts nope n, rope r; values v), on
its normed input u at position t::

    c_q = RMSNorm(W_qa u);   q = W_qb c_q  in [H, n + r]
    [c_kv ; k_r] = W_kva u;  c = RMSNorm(c_kv)  in R^kv_r
    k_rope = RoPE_t(k_r) (one head, shared); q_rope = RoPE_t(q[:, n:])
    RoPE on interleaved pairs (2j, 2j + 1), cos and sin times
      m(mscale) / m(mscale_all_dim),  m(a) = 0.1 a ln(factor) + 1, at
      inv_freq_j = theta^(-2j / r) ((1 - ramp_j) + ramp_j / factor),
      ramp_j = clip((j - low) / (high - low), 0, 1),
      low  = floor(r ln(L0 / (2 pi beta_fast)) / (2 ln theta)),
      high = ceil (r ln(L0 / (2 pi beta_slow)) / (2 ln theta))
    [k_nope_h ; v_h] = W_kvb,h c       (kept as W_UK [kv_r, H, n] and
                                        W_UV [kv_r, H, v])
    score_h(t, j) = (q_nope_h . k_nope_h(j) + q_rope_h . k_rope(j))
                    * m(mscale_all_dim)^2 / sqrt(n + r),  causal
    o_h = softmax_j(.) v_h(j);   out = W_o concat_h(o_h)

Routed (N router outputs in G groups of N / G consecutive ids, g groups
kept, k a token, scale)::

    sc = sigmoid(W_r h) over all N, float32     (no selection bias)
    a group's score = the sum of its two largest sc
    kept = the g groups of the largest score    (ties: the lower index)
    chosen = the k largest sc among the kept groups' outputs
    w_e = scale * sc_e / sum_{e' chosen} sc_e'  for e in chosen
    Routed(h) = sum_{e chosen, e HELD HERE} w_e SwiGLU_e(h)
"""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp

from benchmarks.harness import cells
from benchmarks.harness.reference import _mm
from benchmarks.harness.weights import seed_key

# unchanged helpers, not copied a fourth time (ROADMAP W0 folds them)
_longcat = cells.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "longcat.py"),
    "benchmarks_arch_longcat")
_is_spec, _frozen, _pad_to = (_longcat._is_spec, _longcat._frozen,
                              _longcat._pad_to)
_rms, _swiglu, _attend = _longcat._rms, _longcat._swiglu, _longcat._attend
_draw = _longcat._laguna._draw
embed, head, ATTN_BLOCK = _longcat.embed, _longcat.head, _longcat.ATTN_BLOCK

# What `quant` may name beside "int8" / "fp8": the reference with one of
# this model's mechanisms taken out, in float32 - "no_yarn" plain
# frequencies AND no factor in the scale (= "plain_rope" + "scale_1"),
# "no_groups" the choice over all router outputs.
CONTROLS_OF_THE_MODEL = ("no_yarn", "plain_rope", "scale_1", "no_groups")


def _control(arch, quant):
    """(the arch the control computes, the `quant` of its products)."""
    if quant not in CONTROLS_OF_THE_MODEL:
        return arch, quant
    arch = dict(arch)
    if quant in ("no_yarn", "plain_rope"):
        arch["plain_rope"] = True
    if quant in ("no_yarn", "scale_1"):
        arch["scale_1"] = True
    if quant == "no_groups":
        arch["n_group"] = arch["topk_group"] = 1
    return arch, None


# ---- the rotary rule ---------------------------------------------------------
def mscale(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(arch):
    """(inv_freq [r / 2] as a tuple of Python floats, the factor on cos
    and sin, the factor on the softmax scale) - the docstring's
    arithmetic; (plain frequencies, 1, 1) without `rope_scaling`."""
    r, theta = arch["qk_rope_head_dim"], float(arch["rope_theta"])
    plain = [theta ** (-2.0 * j / r) for j in range(r // 2)]
    rs = arch.get("rope_scaling")
    if not rs:
        return tuple(plain), 1.0, 1.0
    if rs["type"] != "yarn":
        raise ValueError(rs["type"])
    factor, L0 = rs["factor"], rs["original_max_position_embeddings"]

    def turns(n):           # the index that turns n times over L0
        return r * math.log(L0 / (n * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns(rs["beta_fast"])), 0)
    high = min(math.ceil(turns(rs["beta_slow"])), r - 1)
    inv = []
    for j, f in enumerate(plain):
        ramp = min(max((j - low) / max(high - low, 1e-3), 0.0), 1.0)
        inv.append(f * ((1.0 - ramp) + ramp / factor))
    all_dim = mscale(factor, rs["mscale_all_dim"])
    return (tuple(plain if arch.get("plain_rope") else inv),
            1.0 if arch.get("plain_rope")
            else mscale(factor, rs["mscale"]) / all_dim,
            1.0 if arch.get("scale_1") else all_dim ** 2)


# ---- the program's model ----------------------------------------------
def program_model(arch, *, max_len, attn_impl=None, dtype=None):
    """`TransformerLM` for this `arch`: the one place that knows its
    field names for a model of A.X-K1 layers."""
    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.parallel.latent_attention import LatentSpec
    from horovod_tpu.parallel.tensor import RopeSpec

    _, on_cos_sin, factor = yarn(arch)
    rs = arch.get("rope_scaling") or {}
    rope = RopeSpec(
        theta=arch["rope_theta"], yarn_factor=rs.get("factor"),
        yarn_original_len=rs.get("original_max_position_embeddings", 0),
        yarn_beta_fast=rs.get("beta_fast", 32.0),
        yarn_beta_slow=rs.get("beta_slow", 1.0), scale=on_cos_sin)
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
            rope=rope, softmax_factor=factor),
        mlp_impl="swiglu", mlp_hidden=arch["dense_hidden"],
        mlp_only_layers=tuple(arch["dense_layers"]),
        moe_every=1, moe_impl="dropless",
        num_experts=arch["num_experts"], moe_k=arch["experts_per_token"],
        moe_hidden=arch["expert_hidden"],
        moe_held=tuple(arch["experts_held"]),
        moe_shared_hidden=arch["shared_hidden"],
        moe_router=arch["router"], moe_router_bias=arch["router_bias"],
        moe_normalize=arch["norm_topk"], moe_scale=arch["routed_scale"],
        moe_groups=(arch["n_group"], arch["topk_group"]),
        dtype=jnp.dtype(dtype or arch["compute_dtype"]))
    if attn_impl:
        kw["attn_impl"] = attn_impl
    return TransformerLM(**kw)


# ---- weights from the seed --------------------------------------------
def layout(arch, max_len=None):
    """Nested dict of (shape, kind), the parameter tree the program's
    model declares. Kinds: 'matrix' normal(0, 0.02) kept in the matrix
    dtype; 'scale' 1 + normal(0, 0.02). No selection bias exists."""
    del max_len                         # no position table
    d, V, H = arch["hidden_size"], arch["vocab_size"], arch["num_heads"]
    qr, kvr = arch["q_lora_rank"], arch["kv_lora_rank"]
    n, r, v = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
               arch["v_head_dim"])
    (_, E), m = arch["experts_held"], arch["expert_hidden"]
    if arch["router_bias"] or arch["router"] != "sigmoid":
        raise ValueError("the A.X-K1 gate is sigmoid, without a bias")

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
    moe = {"router": ((d, arch["num_experts"]), "matrix"),
           "w_gate": ((E, d, m), "matrix"), "w_up": ((E, d, m), "matrix"),
           "w_down": ((E, m, d), "matrix"),
           "shared": swiglu(arch["shared_hidden"])}
    tree = {"embed": ((V, d), "matrix"), "lm_head": ((V, d), "matrix"),
            "ln_f": norm()}
    if arch["tied_head"]:
        del tree["lm_head"]
    for i in range(arch["num_layers"]):
        blk = {"mla": mla, "ln_attn": norm(), "ln_mlp": norm()}
        if i in arch["dense_layers"]:
            blk["mlp"] = swiglu(arch["dense_hidden"])
        else:
            blk["moe"] = moe
        tree[f"block_{i}"] = blk
    return tree


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
    position x inv_freq_j, cos and sin times the rule's factor."""
    inv, on_cos_sin, _ = yarn(arch)
    ang = (positions.astype(jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32))
    cos = jnp.cos(ang)[:, None, :] * on_cos_sin
    sin = jnp.sin(ang)[:, None, :] * on_cos_sin
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def mla(arch, p, u, quant=None):
    """u [S, d] -> [S, d]: one latent-attention sublayer, expanded."""
    S, H = u.shape[0], arch["num_heads"]
    kvr, n, r, v = (arch["kv_lora_rank"], arch["qk_nope_head_dim"],
                    arch["qk_rope_head_dim"], arch["v_head_dim"])
    eps = arch["norm_eps"]
    pos = jnp.arange(S)
    cq = _rms(_mm(u, p["q_a"]["kernel"], quant), p["q_a_norm"]["scale"],
              eps)
    q = _mm(cq, p["q_b"]["kernel"], quant).reshape(S, H, n + r)
    kv = _mm(u, p["kv_a"]["kernel"], quant)
    c = _rms(kv[:, :kvr], p["kv_a_norm"]["scale"], eps)
    k_rope = rotate(arch, kv[:, None, kvr:], pos)            # [S, 1, r]
    q = jnp.concatenate([q[..., :n], rotate(arch, q[..., n:], pos)], -1)
    k = jnp.concatenate(
        [_mm(c, p["k_up"].reshape(kvr, H * n), quant).reshape(S, H, n),
         jnp.broadcast_to(k_rope, (S, H, r))], -1)
    val = _mm(c, p["v_up"].reshape(kvr, H * v), quant).reshape(S, H, v)
    q = q * (yarn(arch)[2] * (n + r) ** -0.5)
    blk = math.gcd(S, ATTN_BLOCK)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, 0)
        return _attend(qb, k, val, start + jnp.arange(blk), pos)

    o = jax.lax.map(rows, jnp.arange(0, S, blk)).reshape(S, H * v)
    return _mm(o, p["out"]["kernel"], quant)


def route(arch, p, x, quant=None):
    """(chosen [S, k] ids over ALL router outputs, weights [S, k])."""
    sc = jax.nn.sigmoid(_mm(x, p["router"], quant))
    S, N = sc.shape
    G, g, k = arch["n_group"], arch["topk_group"], arch["experts_per_token"]
    pick = sc
    if (G, g) != (1, 1):
        grouped = sc.reshape(S, G, N // G)
        score = jax.lax.top_k(grouped, 2)[0].sum(-1)             # [S, G]
        _, kept = jax.lax.top_k(score, g)
        keep = (kept[..., None] == jnp.arange(G)).any(-2)        # [S, G]
        pick = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(S, N)
    _, chosen = jax.lax.top_k(pick, k)
    w = jnp.take_along_axis(sc, chosen, axis=-1)
    if arch["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return chosen, arch["routed_scale"] * w


def moe(arch, p, x, quant=None, held=None, shared=True):
    """x [S, d] -> [S, d]: the shared expert's part (``shared=False``
    leaves it out - the share test counts it once) plus the part that
    the experts `held` = (first, count) give: a loop over those
    experts, each applied to every token and weighted by the token's
    weight for it (0 where it was not chosen). `p["w_*"]` hold exactly
    those experts."""
    first, n = held or arch["experts_held"]
    chosen, w = route(arch, p, x, quant)
    y = _swiglu(x, p["shared"], quant) if shared else jnp.zeros_like(x)

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


# The layer as two pieces, each small enough to be cast to float32 at
# once beside the whole cut's bf16 weights.
def piece(arch, step, p, x, quant=None):
    if step == "mla":
        return x + mla(arch, p["mla"], _norm(arch, p, "ln_attn", x), quant)
    if step == "mlp":
        return x + _swiglu(_norm(arch, p, "ln_mlp", x), p["mlp"], quant)
    if step == "moe":
        return x + moe(arch, p["moe"], _norm(arch, p, "ln_mlp", x), quant)
    raise ValueError(step)


def steps(arch, i):
    return ("mla", "mlp" if i in arch["dense_layers"] else "moe")


def logits(arch, params, tokens, quant=None):
    """tokens [S] -> [S, V]: the whole forward pass."""
    arch, quant = _control(arch, quant)
    x = embed(arch, params, tokens)
    for i in range(arch["num_layers"]):
        for step in steps(arch, i):
            x = piece(arch, step, params[f"block_{i}"], x, quant)
    return head(arch, params, x, quant)


def routing(arch, params, tokens):
    """The reference's chosen ids: [expert layers, S, k], sorted per
    token."""
    x = embed(arch, params, jnp.asarray(tokens))
    out = []
    for i in range(arch["num_layers"]):
        p = params[f"block_{i}"]
        x = piece(arch, "mla", p, x)
        if i not in arch["dense_layers"]:
            out.append(jnp.sort(route(
                arch, p["moe"], _norm(arch, p, "ln_mlp", x))[0], axis=-1))
        x = piece(arch, steps(arch, i)[1], p, x)
    return jnp.stack(out)


@functools.lru_cache(maxsize=None)
def _jitted(what, arch_json, quant):
    arch = json.loads(arch_json)
    if what == "embed":
        return jax.jit(functools.partial(embed, arch))
    if what in ("mla", "mlp", "moe"):
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
    arch, quant = _control(arch, quant)
    P, n = len(prompt), len(served)
    n_rows = _pad_to(n, row_block)
    seq = np.zeros(_pad_to(P - 1 + n_rows, seq_block), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = served[:n - 1]
    x = _fn("embed", arch)(params, jnp.asarray(seq))
    for i in range(arch["num_layers"]):
        for step in steps(arch, i):
            x = _fn(step, arch, quant)(params[f"block_{i}"], x)
    return _fn("head_rows", arch, quant)(params, x, P - 1,
                                         n_rows=n_rows)[:n]


def expert_routing(arch, params, tokens, seq_block=ATTN_BLOCK):
    """`routing` over tokens padded to a block: [expert layers, len, k],
    a row an EXPERT layer. Not under the name `reference_routing`:
    `serve_arch.routing_flips` takes that name as a promise of one row
    a LAYER and reads the program's `chosen` of every block, which a
    dense leading layer does not sow (PERF.md §7); the tests compare
    the two sides' routing themselves."""
    import numpy as np
    seq = np.zeros(_pad_to(len(tokens), seq_block), np.int32)
    seq[:len(tokens)] = tokens
    return np.asarray(_fn("routing", arch)(params, seq))[:, :len(tokens)]


# ---- required bytes and operations of one decode tick --------------------
def expert_layers(arch):
    return arch["num_layers"] - len(arch["dense_layers"])


def expert_params(arch):
    return 3 * arch["hidden_size"] * arch["expert_hidden"]


def other_matmul_params(arch):
    """Parameters outside the routed experts that a decoded token
    multiplies: every layer's latent attention (in the absorbed step
    W_UK and W_UV are multiplied once a row like any other matrix), the
    dense FFN, the routers, the shared experts, and the head (the
    embedding lookup multiplies nothing)."""
    tree = layout(arch)
    total = math.prod(tree["lm_head" if not arch["tied_head"]
                           else "embed"][0])
    for i in range(arch["num_layers"]):
        blk = dict(tree[f"block_{i}"])
        if "moe" in blk:
            blk["moe"] = {"router": blk["moe"]["router"],
                          "shared": blk["moe"]["shared"]}
        total += sum(math.prod(s) for s, kind in jax.tree.leaves(
            blk, is_leaf=_is_spec) if kind == "matrix")
    return total


def latent_row(arch):
    """Numbers a cached position holds in one layer."""
    return arch["kv_lora_rank"] + arch["qk_rope_head_dim"]


def latent_flops_per_position(arch):
    """Absorbed scores and weighted sum of one cached position in one
    layer: H heads x (a row for the score + its latent part for the
    sum), 2 flops a multiply-add."""
    return 2 * arch["num_heads"] * (latent_row(arch)
                                    + arch["kv_lora_rank"])


def tick_bytes(arch, lanes_decoding, context_sum, experts_hit,
               weight_bytes=2, cache_bytes=2):
    """Bytes one tick must move: the weights of the experts that got a
    pair (`experts_hit`, summed over layers), every other weight once,
    the latent rows of the cached positions (`context_sum`) in every
    layer ONCE (keys and values are the same bytes), and one row a
    lane and layer written."""
    return (experts_hit * expert_params(arch) * weight_bytes
            + other_matmul_params(arch) * weight_bytes
            + (context_sum + lanes_decoding) * arch["num_layers"]
            * latent_row(arch) * cache_bytes)


def tick_flops(arch, lanes_decoding, context_sum, pairs):
    """Flops one tick must do: 2 per parameter a row multiplies (the
    other weights per decoding lane, an expert per held pair), and the
    absorbed attention over the cached positions in every layer."""
    return (2 * other_matmul_params(arch) * lanes_decoding
            + 2 * expert_params(arch) * pairs
            + arch["num_layers"] * latent_flops_per_position(arch)
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
    """(seconds, "bytes" | "flops") of ONE layer's decode-kernel call:
    the shared kernel at LongCat's shape (64 heads over rows of 512 +
    64), so LongCat's count - `arch/longcat.py`."""
    return _longcat.latent_decode_least_seconds(
        arch, peaks, lanes_decoding=lanes_decoding,
        context_sum=context_sum, cache_bytes=cache_bytes)
