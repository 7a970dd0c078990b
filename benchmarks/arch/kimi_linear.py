"""Architecture module `kimi_linear`: everything the benchmark knows of
the Kimi Linear layer period (three delta-rule linear-attention layers
with per-channel decay - KDA, arXiv:2510.26692 - to one latent-attention
layer WITHOUT positions and without a query rank; a dense SwiGLU FFN in
the leading layer and, in every other, sigmoid-routed experts beside one
shared expert), for kind `serve_arch`.

A configuration names its module (`"arch_module": "kimi_linear"`) and the
kind takes from it, and from nowhere else:

    program_model(arch, max_len, attn_impl)   the program's model
    layout / make_params / check_layout / count   weights from --seed
    served_logits(arch, params, prompt, served, quant)   the plain reference
    expert_routing(arch, params, tokens)    the reference's chosen ids
    tick_least_seconds(...), kda_step_least_seconds(...),
    latent_decode_least_seconds(...) and the counts behind them

THE PLAIN REFERENCE is the part from `embed` down: the forward pass in
`jax.numpy`, float32, every product at `Precision.HIGHEST`; the KDA
layers position by position exactly as the recurrence is written, the
latent layers in the EXPANDED equations (keys and values made from the
latent, a head at a time as any softmax attention) with the queries a
block of `ATTN_BLOCK` rows at a time, a loop over the experts held; no
cache, no kernels, no batching; each sublayer a jitted piece of its own
that casts a matrix at a time. It imports nothing of the program. Given
the chip's share (the experts held, the sliced vocabulary) it scores all
the router's outputs and leaves out what the absent experts would add,
as the program does. `quant` is the control: "int8" / "fp8" as in
`harness/reference.py`, and the controls that only this model can fail -
`CONTROLS_OF_THE_MODEL`.

One layer i (0-based), on input x (d = hidden; RMSNorm eps everywhere;
no biases; a final RMSNorm, an untied head)::

    x1 = x + Mixer_i(norm_a(x));   h = norm_m(x1)
    y  = x1 + SwiGLU_dense(h)                      in `dense_layers`
    y  = x1 + Routed(h) + SwiGLU_shared(h)         in every other

KDA (`layer_kinds[i] == "kda"`; H heads, Dk = Dv = D; K = 4 taps), on
its normed input u::

    [q~ ; k~ ; v~] = W_qkv u;  each channel through a causal depthwise
    convolution of K taps, then SiLU;  q = l2(q) / sqrt(D), k = l2(k)
    g_t = -exp(A_log_h) softplus(W_fb W_fa u_t + dt_bias)   in R^{H x D}
    beta_t = sigmoid(W_b u_t)  in (0, 1)^H   (`kda_neg_eigval`: x 2)
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t;  out = W_o(RMSNorm_head(o_t) * sigmoid(W_gb W_ga u_t))

MLA (`"mla"`; H heads; no query rank; kv rank r_kv; head parts nope n,
"rope" r - NOT rotated (`mla_use_nope`); values v)::

    q = W_q u  in [H, n + r];   [c~ ; k_r] = W_kva u;  c = RMSNorm(c~)
    k_nope_h = W_UK,h c,  v_h = W_UV,h c
    score_h(t, j) = (q_h[:n] . k_nope_h(j) + q_h[n:] . k_r(j)) / sqrt(n + r)
    causal softmax over j;  out = W_o concat_h(sum_j p_hj v_h(j))

Routed (N router outputs, k a token, scale; ONE group: no group limit)::

    s = sigmoid(W_r h) over all N, float32;  chosen = the k largest of
    s + b (b the selection bias: for the choice only)
    w_e = scale * s_e / sum_{e' chosen} s_e'
    Routed(h) = sum_{e chosen, e HELD HERE} w_e SwiGLU_e(h)
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

# unchanged helpers, not copied a fifth time (ROADMAP W0 folds them)
_here = os.path.dirname(os.path.abspath(__file__))
_longcat = cells.load_module(os.path.join(_here, "longcat.py"),
                             "benchmarks_arch_longcat")
_solar = cells.load_module(os.path.join(_here, "solar_open2.py"),
                           "benchmarks_arch_solar_open2")
_is_spec, _frozen, _pad_to = (_longcat._is_spec, _longcat._frozen,
                              _longcat._pad_to)
_rms, _swiglu, _attend = _longcat._rms, _longcat._swiglu, _longcat._attend
embed, head, ATTN_BLOCK = _longcat.embed, _longcat.head, _longcat.ATTN_BLOCK
_draw = _solar._draw        # the kinds of a KDA layer's leaves too

KDA, MLA = "kda", "mla"
CONV_TAPS = 4           # `short_conv_kernel_size`; the program's constant

# What `quant` may name beside "int8" / "fp8": the reference with one of
# this model's switches thrown, in float32 - "beta2" beta = 2 sigmoid
# (`allow_neg_eigval`: Solar-Open2's layer under this model's name),
# "rotated" plain RoPE (theta 1e4, interleaved pairs) on the 64-wide part
# of q and on k_r (the DeepSeek family's layer with its default on),
# "state_bf16" the recurrent state rounded to bfloat16 after every
# position (the precision below the one the configuration states for it).
CONTROLS_OF_THE_MODEL = ("beta2", "rotated", "state_bf16")


def _control(arch, quant):
    """(the arch the control computes, the `quant` of its products)."""
    if quant not in CONTROLS_OF_THE_MODEL:
        return arch, quant
    arch = dict(arch)
    if quant == "beta2":
        arch["kda_neg_eigval"] = True
    if quant == "rotated":
        arch["mla_use_nope"] = False
    if quant == "state_bf16":
        arch["state_bf16"] = True
    return arch, None


# ---- the program's model ----------------------------------------------
def program_model(arch, *, max_len, attn_impl=None, dtype=None):
    """`TransformerLM` for this `arch`: the one place that knows its
    field names for a model of Kimi Linear layers."""
    from horovod_tpu.models.transformer import TransformerLM
    from horovod_tpu.parallel.latent_attention import LatentSpec

    if arch["head_dim"] != arch["v_head_dim"]:
        raise ValueError("the program's model has ONE head width: the "
                         "KDA head and the latent layers' value head")
    kw = dict(
        vocab_size=arch["vocab_size"], num_layers=arch["num_layers"],
        hidden_size=arch["hidden_size"], num_heads=arch["num_heads"],
        head_dim=arch["head_dim"], pos_emb="none",
        rope_theta=arch["rope_theta"], max_len=int(max_len),
        norm="rmsnorm", ln_eps=arch["norm_eps"],
        tied_head=arch["tied_head"],
        layer_kinds=tuple(arch["layer_kinds"]),
        kda_neg_eigval=arch["kda_neg_eigval"],
        latent=LatentSpec(
            q_rank=arch["q_lora_rank"], kv_rank=arch["kv_lora_rank"],
            nope_dim=arch["qk_nope_head_dim"],
            rope_dim=arch["qk_rope_head_dim"], v_dim=arch["v_head_dim"],
            rotate=not arch["mla_use_nope"]),
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
    model declares. Kinds as `arch/solar_open2.py` draws them: 'matrix'
    normal(0, 0.02) kept in the matrix dtype; 'scale' 1 + normal(0,
    0.02); 'bias' normal(0, 0.02) (the selection bias); 'conv' normal(0,
    0.5) (the taps); 'a_log' log of uniform(1, 16) and 'dt_bias' the
    inverse softplus of log-uniform(0.001, 0.1)."""
    del max_len                         # no position table
    d, V, H = arch["hidden_size"], arch["vocab_size"], arch["num_heads"]
    D, K = arch["head_dim"], CONV_TAPS
    F = H * D
    kvr, n, r, v = (arch["kv_lora_rank"], arch["qk_nope_head_dim"],
                    arch["qk_rope_head_dim"], arch["v_head_dim"])
    (_, E), m = arch["experts_held"], arch["expert_hidden"]
    if arch["q_lora_rank"] is not None:
        raise ValueError("Kimi Linear's latent layers have no query rank")
    if arch["router"] != "sigmoid" or not arch["router_bias"]:
        raise ValueError("the gate is sigmoid with a selection bias")

    def dense(i, o):
        return {"kernel": ((i, o), "matrix")}

    def norm(width=d):
        return {"scale": ((width,), "scale")}

    def swiglu(width):
        return {"gate": dense(d, width), "up": dense(d, width),
                "down": dense(width, d)}

    mixers = {
        KDA: {"qkv": dense(d, 3 * F), "conv": ((K, 3 * F), "conv"),
              "f_a": dense(d, D), "f_b": dense(D, F),
              "A_log": ((H,), "a_log"), "dt_bias": ((F,), "dt_bias"),
              "b_proj": dense(d, H),
              "g_a": dense(d, D), "g_b": dense(D, F),
              "o_norm": ((D,), "scale"), "o_proj": dense(F, d)},
        MLA: {"q": dense(d, H * (n + r)),
              "kv_a": dense(d, kvr + r), "kv_a_norm": norm(kvr),
              "k_up": ((kvr, H, n), "matrix"),
              "v_up": ((kvr, H, v), "matrix"),
              "out": dense(H * v, d)},
    }
    moe = {"router": ((d, arch["num_experts"]), "matrix"),
           "router_bias": ((arch["num_experts"],), "bias"),
           "w_gate": ((E, d, m), "matrix"), "w_up": ((E, d, m), "matrix"),
           "w_down": ((E, m, d), "matrix"),
           "shared": swiglu(arch["shared_hidden"])}
    tree = {"embed": ((V, d), "matrix"), "lm_head": ((V, d), "matrix"),
            "ln_f": norm()}
    if arch["tied_head"]:
        del tree["lm_head"]
    for i, kind in enumerate(arch["layer_kinds"]):
        blk = {kind: mixers[kind], "ln_attn": norm(), "ln_mlp": norm()}
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
def kda(arch, p, u, quant=None):
    """u [S, d] -> [S, d]: the gated delta rule with per-channel decay,
    one position at a time, exactly as the recurrence is written."""
    S = u.shape[0]
    H, D, K = arch["num_heads"], arch["head_dim"], CONV_TAPS
    f32 = jnp.float32
    pre = jnp.pad(_mm(u, p["qkv"]["kernel"], quant), ((K - 1, 0), (0, 0)))
    taps = p["conv"].astype(f32)
    y = sum(taps[j] * pre[j:j + S] for j in range(K))
    q, k, v = (t.reshape(S, H, D)
               for t in jnp.split(jax.nn.silu(y), 3, axis=-1))

    def l2(t):
        return t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)

    q, k = l2(q) * D ** -0.5, l2(k)
    low = _mm(_mm(u, p["f_a"]["kernel"], quant), p["f_b"]["kernel"],
              quant)
    g = (-jnp.exp(p["A_log"])[:, None]
         * jax.nn.softplus(low + p["dt_bias"]).reshape(S, H, D))
    beta = jax.nn.sigmoid(_mm(u, p["b_proj"]["kernel"], quant))
    if arch["kda_neg_eigval"]:
        beta = 2.0 * beta

    def step(state, xs):                        # state [H, Dk, Dv]
        q, k, v, g, b = xs
        state = jnp.exp(g)[:, :, None] * state
        kS = jnp.einsum("hd,hde->he", k, state, precision=HIGHEST)
        state = state - b[:, None, None] * k[:, :, None] * kS[:, None, :]
        state = state + b[:, None, None] * k[:, :, None] * v[:, None, :]
        if arch.get("state_bf16"):
            state = state.astype(jnp.bfloat16).astype(f32)
        return state, jnp.einsum("hde,hd->he", state, q,
                                 precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((H, D, D), f32),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid(_mm(_mm(u, p["g_a"]["kernel"], quant),
                              p["g_b"]["kernel"], quant))
    o = _rms(o, p["o_norm"], arch["norm_eps"]).reshape(S, H * D) * gate
    return _mm(o, p["o_proj"]["kernel"], quant)


def rotate(arch, x, positions):
    """The control "rotated": x [S, heads, r] at `positions` [S], pairs
    (2j, 2j + 1) turned by position x theta^(-2j / r)."""
    r = x.shape[-1]
    inv = float(arch["rope_theta"]) ** (
        -2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def mla(arch, p, u, quant=None):
    """u [S, d] -> [S, d]: one latent-attention layer, expanded; no
    position enters unless the control turns the rotation on."""
    S, H = u.shape[0], arch["num_heads"]
    kvr, n, r, v = (arch["kv_lora_rank"], arch["qk_nope_head_dim"],
                    arch["qk_rope_head_dim"], arch["v_head_dim"])
    pos = jnp.arange(S)
    q = _mm(u, p["q"]["kernel"], quant).reshape(S, H, n + r)
    kv = _mm(u, p["kv_a"]["kernel"], quant)
    c = _rms(kv[:, :kvr], p["kv_a_norm"]["scale"], arch["norm_eps"])
    k_r = kv[:, None, kvr:]                                  # [S, 1, r]
    if not arch["mla_use_nope"]:
        k_r = rotate(arch, k_r, pos)
        q = jnp.concatenate([q[..., :n], rotate(arch, q[..., n:], pos)],
                            -1)
    k = jnp.concatenate(
        [_mm(c, p["k_up"].reshape(kvr, H * n), quant).reshape(S, H, n),
         jnp.broadcast_to(k_r, (S, H, r))], -1)
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
    if (arch["n_group"], arch["topk_group"]) != (1, 1):
        raise ValueError("one group: the choice is a plain top-k")
    s = jax.nn.sigmoid(_mm(x, p["router"], quant))
    _, chosen = jax.lax.top_k(s + p["router_bias"],
                              arch["experts_per_token"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
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
    if step == KDA:
        return x + kda(arch, p[KDA], _norm(arch, p, "ln_attn", x), quant)
    if step == MLA:
        return x + mla(arch, p[MLA], _norm(arch, p, "ln_attn", x), quant)
    if step == "mlp":
        return x + _swiglu(_norm(arch, p, "ln_mlp", x), p["mlp"], quant)
    if step == "moe":
        return x + moe(arch, p["moe"], _norm(arch, p, "ln_mlp", x), quant)
    raise ValueError(step)


def steps(arch, i):
    return (arch["layer_kinds"][i],
            "mlp" if i in arch["dense_layers"] else "moe")


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
        mixer, ffn = steps(arch, i)
        x = piece(arch, mixer, p, x)
        if ffn == "moe":
            out.append(jnp.sort(route(
                arch, p["moe"], _norm(arch, p, "ln_mlp", x))[0], axis=-1))
        x = piece(arch, ffn, p, x)
    return jnp.stack(out)


@functools.lru_cache(maxsize=None)
def _jitted(what, arch_json, quant):
    arch = json.loads(arch_json)
    if what == "embed":
        return jax.jit(functools.partial(embed, arch))
    if what in (KDA, MLA, "mlp", "moe"):
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
    dense leading layer does not sow (PERF.md §7)."""
    import numpy as np
    seq = np.zeros(_pad_to(len(tokens), seq_block), np.int32)
    seq[:len(tokens)] = tokens
    return np.asarray(_fn("routing", arch)(params, seq))[:, :len(tokens)]


# ---- required bytes and operations of one decode tick --------------------
def layers_of(arch, kind):
    return sum(k == kind for k in arch["layer_kinds"])


def expert_layers(arch):
    return arch["num_layers"] - len(arch["dense_layers"])


def expert_params(arch):
    return 3 * arch["hidden_size"] * arch["expert_hidden"]


def other_matmul_params(arch):
    """Parameters outside the routed experts that a decoded token
    multiplies: every layer's mixer (in the absorbed step W_UK and W_UV
    are multiplied once a row like any other matrix), the dense FFN,
    the routers, the shared experts, and the head (the embedding lookup
    multiplies nothing)."""
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


def state_bytes_per_lane(arch):
    """A lane's recurrent state (float32) and convolution tails (bf16)
    over the KDA layers."""
    H, D, K = arch["num_heads"], arch["head_dim"], CONV_TAPS
    return layers_of(arch, KDA) * (H * D * D * 4
                                   + (K - 1) * 3 * H * D * 2)


def latent_row(arch):
    """Numbers a cached position holds in one latent layer."""
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
    each decoding lane's recurrent state and convolution tail read and
    written in every KDA layer, the latent rows of the cached positions
    (`context_sum`) in every latent layer ONCE (keys and values are the
    same bytes), and one row a lane and latent layer written."""
    return (experts_hit * expert_params(arch) * weight_bytes
            + other_matmul_params(arch) * weight_bytes
            + 2 * lanes_decoding * state_bytes_per_lane(arch)
            + (context_sum + lanes_decoding) * layers_of(arch, MLA)
            * latent_row(arch) * cache_bytes)


def tick_flops(arch, lanes_decoding, context_sum, pairs):
    """Flops one tick must do: 2 per parameter a row multiplies (the
    other weights per decoding lane, an expert per held pair), the
    absorbed attention over the cached positions in every latent layer,
    and the state's decay, two rank-one updates and read per KDA
    head."""
    H, D = arch["num_heads"], arch["head_dim"]
    return (2 * other_matmul_params(arch) * lanes_decoding
            + 2 * expert_params(arch) * pairs
            + layers_of(arch, MLA) * latent_flops_per_position(arch)
            * context_sum
            + 7 * layers_of(arch, KDA) * H * D * D * lanes_decoding)


def _least(n_bytes, n_flops, peaks):
    t_b = n_bytes / peaks["hbm_bytes_per_s"]
    t_f = n_flops / peaks["bf16_flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def tick_least_seconds(arch, peaks, *, lanes_decoding, context_sum,
                       experts_hit, pairs):
    """(seconds, "bytes" | "flops"): the least time the chip could
    take for what the tick was asked to do."""
    return _least(
        tick_bytes(arch, lanes_decoding, context_sum, experts_hit),
        tick_flops(arch, lanes_decoding, context_sum, pairs), peaks)


def kda_step_least_seconds(arch, peaks, *, lanes_decoding):
    """(seconds, "bytes" | "flops") of ONE KDA layer's state-step call
    (`ops/kda_step.py`): the decoding lanes' float32 state read and
    written, q, k, g (float32 [H, D]) v and beta in and o out, against
    7 flops a state element. Least bytes only: what the call copies
    through for lanes that do not decode is not asked. The
    convolution's tail is the layer's, not the call's."""
    H, D = arch["num_heads"], arch["head_dim"]
    state = H * D * D * 4
    rows = (5 * H * D + H) * 4              # q k v g in, o out, beta
    return _least(lanes_decoding * (2 * state + rows),
                  7 * H * D * D * lanes_decoding, peaks)


def latent_decode_least_seconds(arch, peaks, *, lanes_decoding,
                                context_sum, cache_bytes=2):
    """(seconds, "bytes" | "flops") of ONE latent layer's decode-kernel
    call: the shared kernel over rows of 512 + 64, LongCat's count at
    this model's head count - `arch/longcat.py`."""
    return _longcat.latent_decode_least_seconds(
        arch, peaks, lanes_decoding=lanes_decoding,
        context_sum=context_sum, cache_bytes=cache_bytes)
