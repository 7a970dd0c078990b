"""Architecture module `laguna`: everything the benchmark knows of the
Laguna layer period (softmax layers of two kinds - full attention and
sliding-window attention - with different query-head counts over the
same K/V heads, a per-head output gate, a rotary rule a kind; a leading
dense SwiGLU layer, then softmax-routed dropless experts with a shared
expert), for kind `serve_arch`.

A configuration names its module (`"arch_module": "laguna"`) and the
kind takes from it, and from nowhere else:

    program_model(arch, max_len, attn_impl)   the program's model
    layout / make_params / check_layout / count   weights from --seed
    served_logits(arch, params, prompt, served, quant)   the plain reference
    routing(arch, params, tokens)              the reference's chosen experts
    tick_least_seconds(...) and the byte counts behind it

THE PLAIN REFERENCE is the part from `embed` down: the forward pass in
`jax.numpy`, float32, every product at `Precision.HIGHEST`, a loop over
the experts held, no cache, no kernels, no batching; the queries of a
layer go through attention a block at a time (`ATTN_BLOCK` rows against
every key they may see), so that a 10 k-token request fits. It imports
nothing of the program. Given the chip's share (the experts held, the
sliced vocabulary) it leaves out what the absent experts would add, as
the program does. `quant` is the control, as in `harness/reference.py`.

Layer equations (x = the block's input after its RMSNorm; h = x_in +
Attn(norm(x_in)), y = h + FFN(norm(h)); a final RMSNorm, an untied
head):

Attention of kind k (`arch["attention"][k]`: H heads, window, rope):
q = W_q x in [H, D]; k, v = W_k x, W_v x in [Hkv, D]; the first r D
dimensions of q and k rotated at the absolute position (half-split
pairs inside that part), the rest passed through; scores q k / sqrt(D),
causal, and under a window W key j is seen by query i iff
i - W < j <= i; o_h = softmax(.) v; g = sigmoid(W_g x) in [H], one
scalar a head; out = W_o concat_h(g_h o_h).

Rotary rule: plain inv_freq_j = theta^(-2j/d_r), d_r = r D. YaRN:
c(n) = d_r ln(L0 / (2 pi n)) / (2 ln theta); low = floor(c(beta_fast)),
high = ceil(c(beta_slow)); ramp_j = clip((j - low) / (high - low), 0,
1); inv_freq_j = theta^(-2j/d_r) ((1 - ramp_j) + ramp_j / factor); cos
and sin multiplied by attention_factor.

FFN of a dense layer (`arch["dense_layers"]`): SwiGLU of width
`dense_hidden`. Of every other layer: s = softmax(W_r x) over ALL
experts in float32; the k largest chosen; w = scale s[chosen] /
sum s[chosen]; y = shared(x) + sum over the chosen e HELD HERE of
w_e expert_e(x); all SwiGLU; weights on the outputs.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import HIGHEST, _mm
from benchmarks.harness.weights import seed_key

FULL, SLIDING = "full", "sliding"
SCOPE = {FULL: "attn", SLIDING: "swa"}      # the program's scope names
ATTN_BLOCK = 512        # query rows the reference attends at a time


# ---- the program's model ----------------------------------------------
def _rope_spec(rope):
    from horovod_tpu.parallel.tensor import RopeSpec
    if rope["type"] == "default":
        return RopeSpec(theta=rope["theta"],
                        fraction=rope["partial_rotary_factor"])
    if rope["type"] != "yarn":
        raise ValueError(rope["type"])
    return RopeSpec(
        theta=rope["theta"], fraction=rope["partial_rotary_factor"],
        yarn_factor=rope["factor"],
        yarn_original_len=rope["original_max_position_embeddings"],
        yarn_beta_fast=rope["beta_fast"],
        yarn_beta_slow=rope["beta_slow"],
        scale=rope["attention_factor"])


def program_model(arch, *, max_len, attn_impl=None, dtype=None):
    """`TransformerLM` for this `arch`: the one place that knows its
    field names for a model whose softmax layers are of two kinds."""
    from horovod_tpu.models.transformer import AttnSpec, TransformerLM

    kinds = arch["attention"]
    kw = dict(
        vocab_size=arch["vocab_size"], num_layers=arch["num_layers"],
        hidden_size=arch["hidden_size"],
        num_heads=kinds[arch["layer_kinds"][0]]["num_heads"],
        num_kv_heads=arch["num_kv_heads"], head_dim=arch["head_dim"],
        pos_emb="rope", max_len=int(max_len), norm="rmsnorm",
        ln_eps=arch["norm_eps"], tied_head=arch["tied_head"],
        attn_gate=arch["attn_gate"],
        layer_kinds=tuple(SCOPE[k] for k in arch["layer_kinds"]),
        attn_specs=tuple(
            (SCOPE[k], AttnSpec(num_heads=a["num_heads"],
                                window=a["window"],
                                rope=_rope_spec(a["rope"])))
            for k, a in sorted(kinds.items())),
        mlp_impl="swiglu", mlp_hidden=arch["dense_hidden"],
        mlp_only_layers=tuple(arch["dense_layers"]),
        moe_every=1, moe_impl="dropless",
        num_experts=arch["num_experts"], moe_k=arch["experts_per_token"],
        moe_hidden=arch["expert_hidden"],
        moe_held=tuple(arch["experts_held"]),
        moe_shared_hidden=arch["shared_hidden"],
        moe_router=arch["router"], moe_scale=arch["routed_scale"],
        dtype=jnp.dtype(dtype or arch["compute_dtype"]))
    if attn_impl:
        kw["attn_impl"] = attn_impl
    return TransformerLM(**kw)


# ---- weights from the seed --------------------------------------------
def layout(arch, max_len=None):
    """Nested dict of (shape, kind), the parameter tree the program's
    model declares. Kinds: 'matrix' normal(0, 0.02) kept in the matrix
    dtype; 'scale' 1 + normal(0, 0.02)."""
    del max_len                         # no position table
    d, V = arch["hidden_size"], arch["vocab_size"]
    Hkv, D = arch["num_kv_heads"], arch["head_dim"]
    N, (_, E) = arch["num_experts"], arch["experts_held"]
    m, ms = arch["expert_hidden"], arch["shared_hidden"]

    def dense(i, o):
        return {"kernel": ((i, o), "matrix")}

    def swiglu(width):
        return {"gate": dense(d, width), "up": dense(d, width),
                "down": dense(width, d)}

    def norm():
        return {"scale": ((d,), "scale")}

    def mixer(kind):
        H = arch["attention"][kind]["num_heads"]
        per_head = arch["attn_gate"] == "head"
        return {"qkv": dense(d, (H + 2 * Hkv) * D),
                "gate": dense(d, H if per_head else H * D),
                "out": dense(H * D, d)}

    moe = {"router": ((d, N), "matrix"),
           "w_gate": ((E, d, m), "matrix"), "w_up": ((E, d, m), "matrix"),
           "w_down": ((E, m, d), "matrix"), "shared": swiglu(ms)}
    tree = {"embed": ((V, d), "matrix"), "lm_head": ((V, d), "matrix"),
            "ln_f": norm()}
    if arch["tied_head"]:
        del tree["lm_head"]
    for i, kind in enumerate(arch["layer_kinds"]):
        blk = {SCOPE[kind]: mixer(kind), "ln_attn": norm(),
               "ln_mlp": norm()}
        if i in arch["dense_layers"]:
            blk["mlp"] = swiglu(arch["dense_hidden"])
        else:
            blk["moe"] = moe
        tree[f"block_{i}"] = blk
    return tree


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _frozen(arch):
    """A hashable form of `arch` (it nests): its JSON text."""
    return json.dumps(arch, sort_keys=True)


def _draw(key, shape, kind, matrix_dtype):
    if kind == "matrix":
        return (0.02 * jax.random.normal(key, shape, jnp.float32)
                ).astype(matrix_dtype)
    if kind == "scale":
        return 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    raise ValueError(kind)


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
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _swiglu(x, p, quant):
    return _mm(jax.nn.silu(_mm(x, p["gate"]["kernel"], quant))
               * _mm(x, p["up"]["kernel"], quant),
               p["down"]["kernel"], quant)


def yarn_ramp(rope, head_dim):
    """(low, high): the frequency indices between which YaRN's ramp
    runs."""
    d_r = int(head_dim * rope["partial_rotary_factor"])

    def turns(n):
        return (d_r * math.log(rope["original_max_position_embeddings"]
                               / (n * 2 * math.pi))
                / (2 * math.log(rope["theta"])))

    return (max(math.floor(turns(rope["beta_fast"])), 0),
            min(math.ceil(turns(rope["beta_slow"])), d_r - 1))


def inv_freq(rope, head_dim):
    """[d_r / 2] float32 frequencies of the rotary rule `rope`."""
    d_r = int(head_dim * rope["partial_rotary_factor"])
    j = jnp.arange(d_r // 2, dtype=jnp.float32)
    plain = rope["theta"] ** (-2.0 * j / d_r)
    if rope["type"] == "default":
        return plain
    low, high = yarn_ramp(rope, head_dim)
    ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain * ((1.0 - ramp) + ramp / rope["factor"])


def rotate(rope, x, positions):
    """x [S, H, D] at `positions` [S]: the first d_r dimensions turned
    (pairs j, j + d_r/2), the rest as they are."""
    d_r = int(x.shape[-1] * rope["partial_rotary_factor"])
    half = d_r // 2
    ang = positions.astype(jnp.float32)[:, None] * inv_freq(
        rope, x.shape[-1])
    scale = rope.get("attention_factor", 1.0)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:d_r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., d_r:]], -1)


def _attend(q, k, v, qpos, kpos, window):
    """q [Sq, H, D] at positions qpos against k, v [Sk, Hkv, D] at
    kpos (a negative position is padding): softmax over the keys each
    query may see."""
    D, g = q.shape[-1], q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST) * D ** -0.5
    keep = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
    if window is not None:
        keep &= qpos[:, None] - kpos[None, :] < window
    s = jnp.where(keep, s, -jnp.inf)
    return jnp.einsum("hst,thd->shd", jax.nn.softmax(s, axis=-1), v,
                      precision=HIGHEST)


def attention(arch, kind, p, x, quant=None):
    """x [S, d] -> [S, d]: one softmax layer of `kind`, its queries a
    block of `ATTN_BLOCK` at a time - against every key of the
    sequence in a full layer, against the W + block keys that end at
    the block's last in a sliding one."""
    a = arch["attention"][kind]
    S = x.shape[0]
    H, Hkv, D = a["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    W, rope = a["window"], a["rope"]
    qkv = _mm(x, p["qkv"]["kernel"], quant)
    pos = jnp.arange(S)
    q = rotate(rope, qkv[:, :H * D].reshape(S, H, D), pos)
    k = rotate(rope, qkv[:, H * D:(H + Hkv) * D].reshape(S, Hkv, D), pos)
    v = qkv[:, (H + Hkv) * D:].reshape(S, Hkv, D)
    blk = math.gcd(S, ATTN_BLOCK)
    if W is not None:           # pad W keys in front: positions < 0
        k = jnp.pad(k, ((W, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((W, 0), (0, 0), (0, 0)))

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, 0)
        qpos = start + jnp.arange(blk)
        if W is None:
            return _attend(qb, k, v, qpos, pos, None)
        kb = jax.lax.dynamic_slice_in_dim(k, start, W + blk, 0)
        vb = jax.lax.dynamic_slice_in_dim(v, start, W + blk, 0)
        return _attend(qb, kb, vb, qpos,
                       start - W + jnp.arange(W + blk), W)

    o = jax.lax.map(rows, jnp.arange(0, S, blk)).reshape(S, H, D)
    gate = jax.nn.sigmoid(_mm(x, p["gate"]["kernel"], quant))
    if arch["attn_gate"] == "head":
        gate = gate[:, :, None]                         # [S, H, 1]
    else:
        gate = gate.reshape(S, H, D)
    return _mm((o * gate).reshape(S, H * D), p["out"]["kernel"], quant)


def route(arch, p, x, quant=None):
    """(chosen [S, k] expert ids over ALL experts, weights [S, k])."""
    logits = _mm(x, p["router"], quant)
    if arch["router"] == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(arch["router"])
    w, chosen = jax.lax.top_k(s, arch["experts_per_token"])
    return chosen, arch["routed_scale"] * w / w.sum(-1, keepdims=True)


def moe(arch, p, x, quant=None, held=None):
    """x [S, d] -> [S, d]: the shared expert plus the part of the
    routed result that the experts `held` = (first, count) give - a
    loop over those experts, each applied to every token and weighted
    by the token's weight for it (0 where it was not chosen).
    `p["w_*"]` hold exactly those experts."""
    first, n = held or arch["experts_held"]
    chosen, w = route(arch, p, x, quant)
    y = _swiglu(x, p["shared"], quant)

    def one(y, e):
        gate, up, down, idx = e
        we = jnp.where(chosen == first + idx, w, 0.0).sum(-1)
        ye = _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant),
                 down, quant)
        return y + we[:, None] * ye, None

    y, _ = jax.lax.scan(one, y, (p["w_gate"], p["w_up"], p["w_down"],
                                 jnp.arange(n)))
    return y


def mix(arch, kind, p, x, quant=None):
    """The block's first half: x + Attn(RMSNorm(x))."""
    h = _rms(x, p["ln_attn"]["scale"], arch["norm_eps"])
    return x + attention(arch, kind, p[SCOPE[kind]], h, quant)


def block(arch, i, p, x, quant=None):
    x = mix(arch, arch["layer_kinds"][i], p, x, quant)
    h = _rms(x, p["ln_mlp"]["scale"], arch["norm_eps"])
    if i in arch["dense_layers"]:
        return x + _swiglu(h, p["mlp"], quant)
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
    for i in range(arch["num_layers"]):
        x = block(arch, i, params[f"block_{i}"], x, quant)
    return head(arch, params, x, quant)


def routing(arch, params, tokens):
    """The reference's chosen experts: [expert layers, S, k] ids
    (sorted per token), for the count of routing flips against the
    program's."""
    x = embed(arch, params, jnp.asarray(tokens))
    out = []
    for i, kind in enumerate(arch["layer_kinds"]):
        p = params[f"block_{i}"]
        if i in arch["dense_layers"]:
            x = block(arch, i, p, x)
            continue
        x = mix(arch, kind, p, x)
        h = _rms(x, p["ln_mlp"]["scale"], arch["norm_eps"])
        out.append(jnp.sort(route(arch, p["moe"], h)[0], axis=-1))
        x = x + moe(arch, p["moe"], h)
    return jnp.stack(out)


@functools.lru_cache(maxsize=None)
def _jitted(what, arch_json, quant):
    arch = json.loads(arch_json)
    if what == "embed":
        return jax.jit(functools.partial(embed, arch))
    if isinstance(what, int):
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
                  seq_block=ATTN_BLOCK, row_block=256):
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
    for i in range(arch["num_layers"]):
        x = _fn(i, arch, quant)(params[f"block_{i}"], x)
    return _fn("head_rows", arch, quant)(params, x, P - 1,
                                         n_rows=n_rows)[:n]


def padded_routing(arch, params, tokens, seq_block=ATTN_BLOCK):
    """`routing` over tokens padded to a block: [expert layers, len,
    k]. (Not under the name `serve_arch.routing_flips` looks for: that
    function reads a `chosen` of EVERY block of the program, and a
    dense leading layer sows none.)"""
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
    multiplies: each layer's attention (projections and gate), the
    dense layers' MLP, each expert layer's router and shared expert,
    and the head (the embedding lookup multiplies nothing)."""
    tree = layout(arch)
    total = math.prod(tree["lm_head" if not arch["tied_head"]
                           else "embed"][0])
    for i in range(arch["num_layers"]):
        blk = dict(tree[f"block_{i}"])
        if "moe" in blk:
            blk["moe"] = {k: v for k, v in blk["moe"].items()
                          if k not in ("w_gate", "w_up", "w_down")}
        total += sum(math.prod(s) for s, kind in jax.tree.leaves(
            blk, is_leaf=_is_spec) if kind == "matrix")
    return total


def kv_bytes_per_position(arch, kind, kv_bytes=2):
    """K and V of one cached position over the layers of `kind`."""
    return (2 * layers_of(arch, kind) * arch["num_kv_heads"]
            * arch["head_dim"] * kv_bytes)


def tick_bytes(arch, lanes_decoding, context_sum, context_window_sum,
               experts_hit, weight_bytes=2):
    """Bytes one tick must move: the weights of the experts that got a
    pair (`experts_hit`, summed over layers), every other weight once,
    the full layers' K/V of the cached positions (`context_sum`), the
    sliding layers' of the positions their rings hold
    (`context_window_sum` = sum of min(context, window)), and one
    position a lane and layer written."""
    return (experts_hit * expert_params(arch) * weight_bytes
            + other_matmul_params(arch) * weight_bytes
            + (context_sum + lanes_decoding)
            * kv_bytes_per_position(arch, FULL)
            + (context_window_sum + lanes_decoding)
            * kv_bytes_per_position(arch, SLIDING))


def tick_flops(arch, lanes_decoding, context_sum, context_window_sum,
               pairs):
    """Flops one tick must do: 2 per parameter a row multiplies (the
    other weights per decoding lane, an expert per held pair), and
    Q K^T and P V of each kind of layer over the positions it sees,
    with that kind's heads."""
    D = arch["head_dim"]

    def attn(kind, positions):
        return (4 * layers_of(arch, kind)
                * arch["attention"][kind]["num_heads"] * D * positions)

    return (2 * other_matmul_params(arch) * lanes_decoding
            + 2 * expert_params(arch) * pairs
            + attn(FULL, context_sum)
            + attn(SLIDING, context_window_sum))


def tick_least_seconds(arch, peaks, *, lanes_decoding, context_sum,
                       context_window_sum, experts_hit, pairs):
    """(seconds, "bytes" | "flops"): the least time the chip could
    take for what the tick was asked to do."""
    t_b = (tick_bytes(arch, lanes_decoding, context_sum,
                      context_window_sum, experts_hit)
           / peaks["hbm_bytes_per_s"])
    t_f = (tick_flops(arch, lanes_decoding, context_sum,
                      context_window_sum, pairs)
           / peaks["bf16_flops_per_s"])
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
