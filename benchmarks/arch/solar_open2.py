"""Architecture module `solar_open2`: everything the benchmark knows of
the Solar-Open2 layer period (a gated NoPE softmax GQA layer, then
delta-rule linear-attention layers with per-channel decay - KDA, Kimi
Linear, arXiv:2510.26692 - every layer followed by a dropless mixture of
experts with a shared expert), for kind `serve_arch`.

A configuration names its module (`"arch_module": "solar_open2"`) and the
kind takes from it, and from nowhere else:

    program_model(arch, max_len, attn_impl)   the program's model
    layout / make_params / check_layout / count   weights from --seed
    served_logits(arch, params, prompt, served, quant)   the plain reference
    routing(arch, params, tokens)              the reference's chosen experts
    tick_least_seconds(...) and the byte counts behind it

THE PLAIN REFERENCE is the part from `embed` down: the forward pass in
`jax.numpy`, float32, every product at `Precision.HIGHEST`, the KDA
layers token by token exactly as the recurrence is written, a loop over
the experts held, no cache, no kernels, no batching. It imports nothing
of the program. Given the chip's share (the experts held, the sliced
vocabulary) it leaves out what the absent experts would add, as the
program does. `quant` is the control, as in `harness/reference.py`.

Layer equations (x = the block's input after its RMSNorm):

KDA (H heads, Dk = Dv = D): q, k, v = SiLU(conv_K(W x)) with a causal
depthwise convolution of K taps; q, k L2-normalised per head, q scaled
by D^-1/2; g_t = -exp(A_log_h) softplus(W_fb W_fa x_t + dt_bias) per
channel, alpha_t = exp(g_t); beta_t = 2 sigmoid(W_b x_t);
S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T;
o_t = S_t^T q_t; y_t = W_o(RMSNorm_head(o_t) * sigmoid(W_gb W_ga x_t)).

GQA: softmax(q k^T / sqrt(D)) causal, no positions;
y = W_o(attn * sigmoid(W_g x)).

Experts: s = sigmoid(W_r x); the k largest of s + b chosen;
w = s[chosen] / sum s[chosen]; y = shared(x) + sum over the chosen e
HELD HERE of w_e expert_e(x); all SwiGLU.

What the published file fixes is fixed here and in the program alike,
not read from `arch`: the convolution's 4 taps, the linear-attention
layers' heads and head size (= the softmax layers'), both low-rank
gates at rank head_dim, chosen weights normalised to one
(`norm_topk_prob` true) and scaled by 1 (`routed_scaling_factor`). A
variant that differs needs the option in the program first.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import HIGHEST, _mm
from benchmarks.harness.weights import seed_key

GQA, KDA = "gqa", "kda"
CONV_TAPS = 4


# ---- the program's model ----------------------------------------------
def program_model(arch, *, max_len, attn_impl=None, dtype=None):
    """`TransformerLM` for this `arch`: the one place that knows its
    field names for a hybrid model."""
    from horovod_tpu.models.transformer import TransformerLM

    kw = dict(
        vocab_size=arch["vocab_size"], num_layers=arch["num_layers"],
        hidden_size=arch["hidden_size"], num_heads=arch["num_heads"],
        num_kv_heads=arch["num_kv_heads"], head_dim=arch["head_dim"],
        pos_emb="none", max_len=int(max_len), norm="rmsnorm",
        ln_eps=arch["norm_eps"], tied_head=arch["tied_head"],
        attn_gate=arch["attn_gate"],
        layer_kinds=tuple({GQA: "attn", KDA: "kda"}[k]
                          for k in arch["layer_kinds"]),
        moe_every=1, moe_impl="dropless",
        num_experts=arch["num_experts"], moe_k=arch["experts_per_token"],
        moe_hidden=arch["expert_hidden"],
        moe_held=tuple(arch["experts_held"]),
        moe_shared_hidden=arch["shared_hidden"],
        dtype=jnp.dtype(dtype or arch["compute_dtype"]))
    if attn_impl:
        kw["attn_impl"] = attn_impl
    return TransformerLM(**kw)


# ---- weights from the seed --------------------------------------------
def layout(arch, max_len=None):
    """Nested dict of (shape, kind), the parameter tree the program's
    model declares. Kinds: 'matrix' normal(0, 0.02) kept in the matrix
    dtype; 'scale' 1 + normal(0, 0.02); 'bias' normal(0, 0.02); 'conv'
    normal(0, 0.5) (a short convolution's taps); 'a_log' log of
    uniform(1, 16) and 'dt_bias' the inverse softplus of log-uniform
    (0.001, 0.1) - the delta-rule family's initial decay range, so that
    heads remember over tens to hundreds of positions."""
    del max_len                         # no position table
    d, V = arch["hidden_size"], arch["vocab_size"]
    H, Hkv, D = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    F, K = H * D, CONV_TAPS
    N, (_, E) = arch["num_experts"], arch["experts_held"]
    m, ms = arch["expert_hidden"], arch["shared_hidden"]

    def dense(i, o):
        return {"kernel": ((i, o), "matrix")}

    def norm():
        return {"scale": ((d,), "scale")}

    mixers = {
        GQA: ("attn", {"qkv": dense(d, (H + 2 * Hkv) * D),
                       "gate": dense(d, H * D),
                       "out": dense(H * D, d)}),
        KDA: ("kda", {"qkv": dense(d, 3 * F),
                      "conv": ((K, 3 * F), "conv"),
                      "f_a": dense(d, D), "f_b": dense(D, F),
                      "A_log": ((H,), "a_log"),
                      "dt_bias": ((F,), "dt_bias"),
                      "b_proj": dense(d, H),
                      "g_a": dense(d, D), "g_b": dense(D, F),
                      "o_norm": ((D,), "scale"),
                      "o_proj": dense(F, d)}),
    }
    moe = {"router": ((d, N), "matrix"),
           "router_bias": ((N,), "bias"),
           "w_gate": ((E, d, m), "matrix"), "w_up": ((E, d, m), "matrix"),
           "w_down": ((E, m, d), "matrix"),
           "shared": {"gate": dense(d, ms), "up": dense(d, ms),
                      "down": dense(ms, d)}}
    tree = {"embed": ((V, d), "matrix"), "lm_head": ((V, d), "matrix"),
            "ln_f": norm()}
    if arch["tied_head"]:
        del tree["lm_head"]
    for i, kind in enumerate(arch["layer_kinds"]):
        name, mixer = mixers[kind]
        tree[f"block_{i}"] = {name: mixer, "ln_attn": norm(),
                              "ln_mlp": norm(), "moe": moe}
    return tree


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _frozen(arch):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in arch.items()))


def _draw(key, shape, kind, matrix_dtype):
    if kind in ("matrix", "conv"):
        std = 0.02 if kind == "matrix" else 0.5
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(matrix_dtype)
    if kind == "scale":
        return 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind == "bias":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
    raise ValueError(kind)


@functools.lru_cache(maxsize=None)
def _maker(arch_items, matrix_dtype):
    spec = layout(dict(arch_items))
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
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _swiglu(x, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant),
               down, quant)


def gqa_mixer(arch, p, x, quant=None):
    """x [S, d] -> [S, d]: causal softmax attention without positions,
    64 query heads on 8 key/value heads, output gated elementwise."""
    S = x.shape[0]
    H, Hkv, D = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    qkv = _mm(x, p["qkv"]["kernel"], quant)
    q = qkv[:, :H * D].reshape(S, H, D)
    k = qkv[:, H * D:(H + Hkv) * D].reshape(S, Hkv, D)
    v = qkv[:, (H + Hkv) * D:].reshape(S, Hkv, D)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(S, H * D)
    gate = jax.nn.sigmoid(_mm(x, p["gate"]["kernel"], quant))
    return _mm(o * gate, p["out"]["kernel"], quant)


def kda_mixer(arch, p, x, quant=None):
    """x [S, d] -> [S, d]: the gated delta rule with per-channel decay,
    one position at a time, exactly as the recurrence is written."""
    S = x.shape[0]
    H, D, K = arch["num_heads"], arch["head_dim"], CONV_TAPS
    eps = arch["norm_eps"]
    f32 = jnp.float32
    u = jnp.pad(_mm(x, p["qkv"]["kernel"], quant), ((K - 1, 0), (0, 0)))
    taps = p["conv"].astype(f32)
    y = sum(taps[j] * u[j:j + S] for j in range(K))
    q, k, v = (t.reshape(S, H, D)
               for t in jnp.split(jax.nn.silu(y), 3, axis=-1))

    def l2(t):
        return t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)

    q, k = l2(q) * D ** -0.5, l2(k)
    low = _mm(_mm(x, p["f_a"]["kernel"], quant), p["f_b"]["kernel"],
              quant)
    g = (-jnp.exp(p["A_log"])[:, None]
         * jax.nn.softplus(low + p["dt_bias"]).reshape(S, H, D))
    beta = 2.0 * jax.nn.sigmoid(_mm(x, p["b_proj"]["kernel"], quant))

    def step(state, xs):                        # state [H, Dk, Dv]
        q, k, v, g, b = xs
        state = jnp.exp(g)[:, :, None] * state
        kS = jnp.einsum("hd,hde->he", k, state, precision=HIGHEST)
        state = state - b[:, None, None] * k[:, :, None] * kS[:, None, :]
        state = state + b[:, None, None] * k[:, :, None] * v[:, None, :]
        return state, jnp.einsum("hde,hd->he", state, q,
                                 precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((H, D, D), f32),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid(_mm(_mm(x, p["g_a"]["kernel"], quant),
                              p["g_b"]["kernel"], quant))
    o = _rms(o, p["o_norm"], eps).reshape(S, H * D) * gate
    return _mm(o, p["o_proj"]["kernel"], quant)


def route(arch, p, x, quant=None):
    """(chosen [S, k] expert ids over ALL experts, weights [S, k])."""
    s = jax.nn.sigmoid(_mm(x, p["router"], quant))
    _, chosen = jax.lax.top_k(s + p["router_bias"],
                              arch["experts_per_token"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, w / w.sum(-1, keepdims=True)


def moe(arch, p, x, quant=None, held=None):
    """x [S, d] -> [S, d]: the shared expert plus the part of the
    routed result that the experts `held` = (first, count) give; a
    loop over those experts, each applied to every token and weighted
    by the token's weight for it (0 where it was not chosen)."""
    first, n = held or arch["experts_held"]
    chosen, w = route(arch, p, x, quant)
    sh = p["shared"]
    y = _swiglu(x, sh["gate"]["kernel"], sh["up"]["kernel"],
                sh["down"]["kernel"], quant)

    def one(y, e):
        gate, up, down, idx = e
        we = jnp.where(chosen == first + idx, w, 0.0).sum(-1)
        return y + we[:, None] * _swiglu(x, gate, up, down, quant), None

    y, _ = jax.lax.scan(one, y, (p["w_gate"], p["w_up"], p["w_down"],
                                 jnp.arange(n)))
    return y


def mix(arch, kind, p, x, quant=None):
    """The block's first half: x + mixer(RMSNorm(x))."""
    h = _rms(x, p["ln_attn"]["scale"], arch["norm_eps"])
    if kind == GQA:
        return x + gqa_mixer(arch, p["attn"], h, quant)
    return x + kda_mixer(arch, p["kda"], h, quant)


def block(arch, kind, p, x, quant=None):
    x = mix(arch, kind, p, x, quant)
    h = _rms(x, p["ln_mlp"]["scale"], arch["norm_eps"])
    return x + moe(arch, p["moe"], h, quant)


def embed(arch, params, tokens):
    return jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)


def head(arch, params, hidden, quant=None):
    h = _rms(hidden, params["ln_f"]["scale"], arch["norm_eps"])
    table = params["embed" if arch["tied_head"] else "lm_head"]
    return _mm(h, table.astype(jnp.float32).T, quant)


def logits(arch, params, tokens, quant=None):
    """tokens [S] -> [S, V]: the whole forward pass."""
    x = embed(arch, params, tokens)
    for i, kind in enumerate(arch["layer_kinds"]):
        x = block(arch, kind, params[f"block_{i}"], x, quant)
    return head(arch, params, x, quant)


def routing(arch, params, tokens):
    """The reference's chosen experts: [layers, S, k] ids (sorted per
    token), for the count of routing flips against the program's."""
    x = embed(arch, params, jnp.asarray(tokens))
    out = []
    for i, kind in enumerate(arch["layer_kinds"]):
        p = params[f"block_{i}"]
        x = mix(arch, kind, p, x)
        h = _rms(x, p["ln_mlp"]["scale"], arch["norm_eps"])
        out.append(jnp.sort(route(arch, p["moe"], h)[0], axis=-1))
        x = x + moe(arch, p["moe"], h)
    return jnp.stack(out)


@functools.lru_cache(maxsize=None)
def _jitted(what, arch_items, quant):
    arch = dict(arch_items)
    if what == "embed":
        return jax.jit(functools.partial(embed, arch))
    if what in (GQA, KDA):
        return jax.jit(functools.partial(block, arch, what, quant=quant))
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


def _pad_to(n, blk):
    return -(-n // blk) * blk


def served_logits(arch, params, prompt, served, quant=None,
                  seq_block=512, row_block=256):
    """Logits [len(served), V] of the reference at each position whose
    next token the system served: one full forward over prompt ++
    served (teacher-forced; everything is causal, so the padding after
    the end reaches nothing), layer by layer, the weights upcast a
    layer at a time. Lengths are padded to blocks so that a few shapes
    compile."""
    import numpy as np
    P, n = len(prompt), len(served)
    n_rows = _pad_to(n, row_block)
    seq = np.zeros(_pad_to(P - 1 + n_rows, seq_block), np.int32)
    seq[:P] = prompt
    seq[P:P + n - 1] = served[:n - 1]
    x = _fn("embed", arch)(params, jnp.asarray(seq))
    for i, kind in enumerate(arch["layer_kinds"]):
        x = _fn(kind, arch, quant)(params[f"block_{i}"], x)
    return _fn("head_rows", arch, quant)(params, x, P - 1,
                                         n_rows=n_rows)[:n]


def reference_routing(arch, params, tokens, seq_block=512):
    """`routing` over tokens padded to a block: [layers, len, k]."""
    import numpy as np
    seq = np.zeros(_pad_to(len(tokens), seq_block), np.int32)
    seq[:len(tokens)] = tokens
    return np.asarray(_fn("routing", arch)(params, seq))[:, :len(tokens)]


# ---- required bytes and operations of one decode tick --------------------
def expert_params(arch):
    return 3 * arch["hidden_size"] * arch["expert_hidden"]


def other_matmul_params(arch):
    """Parameters outside the routed experts that a decoded token
    multiplies: each layer's mixer, router and shared expert, and the
    head (the embedding lookup multiplies nothing)."""
    tree = layout(arch)
    total = math.prod(tree["lm_head" if not arch["tied_head"]
                           else "embed"][0])
    for i in range(arch["num_layers"]):
        blk = dict(tree[f"block_{i}"])
        moe_ = dict(blk.pop("moe"))
        for k in ("w_gate", "w_up", "w_down"):
            moe_.pop(k)
        total += sum(math.prod(s) for s, kind in jax.tree.leaves(
            (blk, moe_), is_leaf=_is_spec) if kind == "matrix")
    return total


def state_bytes_per_lane(arch):
    """A lane's recurrent state (float32) and convolution tails (bf16)
    over the KDA layers: read and written once a tick each."""
    H, D, K = arch["num_heads"], arch["head_dim"], CONV_TAPS
    n = sum(k == KDA for k in arch["layer_kinds"])
    return n * (H * D * D * 4 + (K - 1) * 3 * H * D * 2)


def kv_bytes_per_position(arch, kv_bytes=2):
    n = sum(k == GQA for k in arch["layer_kinds"])
    return 2 * n * arch["num_kv_heads"] * arch["head_dim"] * kv_bytes


def tick_bytes(arch, lanes_decoding, context_sum, experts_hit,
               weight_bytes=2):
    """Bytes one tick must move: the weights of the experts that got a
    pair (`experts_hit`, summed over layers), every other weight once,
    each decoding lane's state read and written, the cached positions'
    K/V read and one position a lane written."""
    return (experts_hit * expert_params(arch) * weight_bytes
            + other_matmul_params(arch) * weight_bytes
            + 2 * lanes_decoding * state_bytes_per_lane(arch)
            + (context_sum + lanes_decoding)
            * kv_bytes_per_position(arch))


def tick_flops(arch, lanes_decoding, context_sum, pairs):
    """Flops one tick must do: 2 per parameter a row multiplies (the
    other weights per decoding lane, an expert per held pair), the
    softmax layers' Q K^T and P V over the context, and the state's
    decay, two rank-one updates and read per KDA head."""
    H, D = arch["num_heads"], arch["head_dim"]
    n_kda = sum(k == KDA for k in arch["layer_kinds"])
    n_gqa = sum(k == GQA for k in arch["layer_kinds"])
    return (2 * other_matmul_params(arch) * lanes_decoding
            + 2 * expert_params(arch) * pairs
            + 4 * n_gqa * arch["num_heads"] * arch["head_dim"]
            * context_sum
            + 7 * n_kda * H * D * D * lanes_decoding)


def tick_least_seconds(arch, peaks, *, lanes_decoding, context_sum,
                       experts_hit, pairs):
    """(seconds, "bytes" | "flops"): the least time the chip could
    take for what the tick was asked to do."""
    t_b = (tick_bytes(arch, lanes_decoding, context_sum, experts_hit)
           / peaks["hbm_bytes_per_s"])
    t_f = (tick_flops(arch, lanes_decoding, context_sum, pairs)
           / peaks["bf16_flops_per_s"])
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
