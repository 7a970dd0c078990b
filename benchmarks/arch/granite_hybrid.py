"""Architecture module `granite_hybrid`: everything the benchmark knows of
the Granite-4.0-H layer pattern (Mamba-2 state-space layers with a scalar
decay a head - "Transformers are SSMs", arXiv:2405.21060 - beside NoPE
softmax GQA layers, every layer followed by a dense SwiGLU, with the
family's four multipliers), for kind `serve_arch`.

A configuration names its module (`"arch_module": "granite_hybrid"`) and
the kind takes from it, and from nowhere else:

    program_model(arch, max_len, attn_impl)   the program's model
    layout / make_params / check_layout / count   weights from --seed
    served_logits(arch, params, prompt, served, quant)   the plain reference
    tick_least_seconds(...), ssm_step_least_seconds(...) and the counts
    behind them

THE PLAIN REFERENCE is the part from `embed` down: the forward pass in
`jax.numpy`, float32, every product at `Precision.HIGHEST`, the
state-space layers position by position exactly as the recurrence is
written (a `lax.scan`, the state a head in the published [P, N] layout),
no cache, no kernels, no batching. It imports nothing of the program.
`quant` is the control, as in `harness/reference.py` ("int8", "fp8": both
operands of every projection rounded) - or one of the state's
(`STATE_CONTROLS`, projections untouched): "state_bf16", the state
rounded to bf16 after every step, and "state_lost", the state started
from zeros at every 128th position. `kinds/serve_arch.py` reads the
first two; `tools/control_readings.py` reads any of them by name.

Layer equations (h = the residual stream, r = residual_scale):

    h += r * mixer(RMSNorm(h));   h += r * SwiGLU(RMSNorm(h))

mamba (H heads of P channels, state N, G groups, K taps): [z | xBC | dt]
= W_in u; xBC = SiLU(conv_K(xBC) + b) with a causal depthwise
convolution; x [H, P], B [G, N], C [G, N] = split(xBC); dt = softplus(dt
+ dt_bias) a head; A = -exp(A_log) a head;
h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) (x) B_t;  y_t = h_t C_t + D x_t;
out = W_out(RMSNorm(y * SiLU(z)) * w), the norm over all H P channels.

attention: softmax(attn_scale q k^T) causal, no positions, H query heads
on Hkv key/value heads; out = W_o attn.

The embedding is multiplied by embed_scale, the logits (tied head) are
divided by logits_divisor.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.reference import HIGHEST, _mm
from benchmarks.harness.weights import seed_key

ATTN, SSM = "attention", "mamba"
# The state's controls, read beside the projections' ("int8", "fp8"): the
# same reference with the state rounded to bf16 after every step (what a
# bf16 state cache would serve), and with the state lost at every 128th
# position (what a prefill chunk that does not start from its lane's
# cached state would serve; 128 is the scheduler's largest chunk).
STATE_CONTROLS = {"state_bf16": {"state_dtype": "bfloat16"},
                  "state_lost": {"lost_every": 128}}


def _ssm_widths(arch):
    """(H, P, N, G, K, I, W): heads, head size, state size, groups,
    taps, the inner width and the convolution's channels x | B | C."""
    H, P, N, G, K = (arch["ssm_heads"], arch["ssm_head_dim"],
                     arch["ssm_state"], arch["ssm_groups"],
                     arch["ssm_conv"])
    return H, P, N, G, K, H * P, H * P + 2 * G * N


# ---- the program's model ----------------------------------------------
def program_model(arch, *, max_len, attn_impl=None, dtype=None):
    """`TransformerLM` for this `arch`: the one place that knows its
    field names for a state-space hybrid."""
    from horovod_tpu.models.transformer import AttnSpec, TransformerLM
    from horovod_tpu.parallel.state_space import SsmSpec

    H, P, N, G, K, _, _ = _ssm_widths(arch)
    kw = dict(
        vocab_size=arch["vocab_size"], num_layers=arch["num_layers"],
        hidden_size=arch["hidden_size"], num_heads=arch["num_heads"],
        num_kv_heads=arch["num_kv_heads"], head_dim=arch["head_dim"],
        pos_emb="none", max_len=int(max_len), norm="rmsnorm",
        ln_eps=arch["norm_eps"], tied_head=arch["tied_head"],
        mlp_impl="swiglu", mlp_hidden=arch["mlp_hidden"],
        layer_kinds=tuple({ATTN: "attn", SSM: "ssm"}[k]
                          for k in arch["layer_kinds"]),
        ssm=SsmSpec(num_heads=H, head_dim=P, state_size=N, groups=G,
                    conv_taps=K, chunk=arch["ssm_chunk"]),
        attn_specs=(("attn", AttnSpec(scale=arch["attn_scale"])),),
        embed_scale=arch["embed_scale"],
        residual_scale=arch["residual_scale"],
        logits_divisor=arch["logits_divisor"],
        dtype=jnp.dtype(dtype or arch["compute_dtype"]))
    if attn_impl:
        kw["attn_impl"] = attn_impl
    return TransformerLM(**kw)


# ---- weights from the seed --------------------------------------------
def layout(arch, max_len=None):
    """Nested dict of (shape, kind), the parameter tree the program's
    model declares. Kinds: 'matrix' normal(0, 0.02) kept in the matrix
    dtype (`arch["matrix_std"]` where a toy names one: at a width of 64
    a projection of 0.02 gives a fifth of what it gives at 2048, and
    the toy's state would carry nothing); 'table' (the embedding, which the tied head reads too)
    normal(0, 0.02 / embed_scale), so that the SCALED embedding enters
    the residual stream at 0.02 as every other cell's does - at 0.02
    itself the tied head would put the input token first by
    construction (12 |E_t|^2 against rows that see noise), and a
    comparison of served tokens would see no rounding short of fp8;
    'scale' 1 + normal(0, 0.02) (the skip D too); 'bias' normal(0,
    0.02); 'conv' normal(0, 0.5) (a short convolution's taps);
    'dt_bias' the inverse softplus of log-uniform(0.001, 0.1), Mamba-2's
    initial range; 'a_log' the log of log-uniform(1/64, 16) - wider
    than Mamba-2's initial uniform(1, 16), as a trained model's heads
    are: a head forgets over 1 / (dt A) positions, here from under one
    to tens of thousands. The slow heads are what makes the STATE the
    thing compared: at uniform(1, 16) y = h C + D x is mostly D x (rms
    0.27 against 0.54 in one layer at these widths) and the reference
    with a bf16 state moves the logits by less than the program's own
    bf16 products do; with heads that hold a context of a thousand
    positions h C is 4.3 against 0.54, and a bf16 state, which drops an
    update under 2^-9 of what it holds, is a different result
    (`STATE_CONTROLS`; the readings are in the cell's limits file)."""
    del max_len                         # no position table
    d, V, m = arch["hidden_size"], arch["vocab_size"], arch["mlp_hidden"]
    H, Hkv, D = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    Hs, _, _, _, K, I, W = _ssm_widths(arch)

    def dense(i, o):
        return {"kernel": ((i, o), "matrix")}

    def norm():
        return {"scale": ((d,), "scale")}

    mixers = {
        ATTN: ("attn", {"qkv": dense(d, (H + 2 * Hkv) * D),
                        "out": dense(H * D, d)}),
        SSM: ("ssm", {"in_proj": dense(d, I + W + Hs),
                      "conv": ((K, W), "conv"),
                      "conv_bias": ((W,), "bias"),
                      "A_log": ((Hs,), "a_log"),
                      "dt_bias": ((Hs,), "dt_bias"),
                      "D": ((Hs,), "scale"),
                      "norm": ((I,), "scale"),
                      "out_proj": dense(I, d)}),
    }
    mlp = {"gate": dense(d, m), "up": dense(d, m), "down": dense(m, d)}
    tree = {"embed": ((V, d), "table"), "lm_head": ((V, d), "matrix"),
            "ln_f": norm()}
    if arch["tied_head"]:
        del tree["lm_head"]
    for i, kind in enumerate(arch["layer_kinds"]):
        name, mixer = mixers[kind]
        tree[f"block_{i}"] = {name: mixer, "ln_attn": norm(),
                              "ln_mlp": norm(), "mlp": mlp}
    return tree


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _frozen(arch):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in arch.items()))


def _draw(key, shape, kind, matrix_dtype, embed_scale, matrix_std):
    if kind in ("matrix", "table", "conv"):
        std = {"matrix": matrix_std, "table": 0.02 / embed_scale,
               "conv": 0.5}[kind]
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(matrix_dtype)
    if kind == "scale":
        return 1.0 + 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind == "bias":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind == "a_log":
        return jax.random.uniform(key, shape, jnp.float32,
                                  math.log(1 / 64), math.log(16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
    raise ValueError(kind)


_draw_leaf = jax.jit(_draw, static_argnums=(1, 2, 3, 4, 5))


def make_params(arch, max_len, seed, matrix_dtype):
    """The whole tree on the default device from `--seed` alone (the
    same key rule as `harness/weights.py`): leaf i is drawn from
    fold_in(key, i). One small program a distinct (shape, kind) - a
    dozen, whatever the depth - and not one program over all 450
    leaves, which takes the compiler five minutes at 40 layers."""
    del max_len
    leaves, treedef = jax.tree.flatten(layout(arch), is_leaf=_is_spec)
    key = seed_key(seed)
    return jax.tree.unflatten(treedef, [
        _draw_leaf(jax.random.fold_in(key, i), shape, kind,
                   str(matrix_dtype), arch["embed_scale"],
                   arch.get("matrix_std", 0.02))
        for i, (shape, kind) in enumerate(leaves)])


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


def attention_mixer(arch, p, x, quant=None):
    """x [S, d] -> [S, d]: causal softmax attention without positions,
    the scores scaled by attn_scale (NOT head_dim ** -0.5)."""
    S = x.shape[0]
    H, Hkv, D = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    qkv = _mm(x, p["qkv"]["kernel"], quant)
    q = qkv[:, :H * D].reshape(S, H, D)
    k = qkv[:, H * D:(H + Hkv) * D].reshape(S, Hkv, D)
    v = qkv[:, (H + Hkv) * D:].reshape(S, Hkv, D)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = (jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST)
         * arch["attn_scale"])
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST).reshape(S, H * D)
    return _mm(o, p["out"]["kernel"], quant)


def mamba_mixer(arch, p, x, quant=None, state_dtype=None, lost_every=None):
    """x [S, d] -> [S, d]: the Mamba-2 mixer, one position at a time,
    exactly as the recurrence is written; the state a head is [P, N].
    The state's controls: `state_dtype` rounds the state after every
    step, `lost_every` starts every block of that many positions from
    zeros."""
    S = x.shape[0]
    H, P, N, G, K, I, W = _ssm_widths(arch)
    f32 = jnp.float32
    proj = _mm(x, p["in_proj"]["kernel"], quant)
    z, xbc, dt = proj[:, :I], proj[:, I:I + W], proj[:, I + W:]
    u = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    taps = p["conv"].astype(f32)
    xbc = jax.nn.silu(sum(taps[j] * u[j:j + S] for j in range(K))
                      + p["conv_bias"])
    xs = xbc[:, :I].reshape(S, H, P)
    Bm = jnp.repeat(xbc[:, I:I + G * N].reshape(S, G, N), H // G, axis=1)
    Cm = jnp.repeat(xbc[:, I + G * N:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])             # [S, H]
    A = -jnp.exp(p["A_log"])

    def step(h, xs_):                                   # h [H, P, N]
        x_t, b_t, c_t, dt_t, t = xs_
        if lost_every is not None:
            h = jnp.where(t % lost_every == 0, 0.0, h)
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if state_dtype is not None:     # not astype there and back: XLA
            info = jnp.finfo(state_dtype)   # drops such a pair on the TPU
            h = jax.lax.reduce_precision(h, info.nexp, info.nmant)
        return h, (h * c_t[:, None, :]).sum(-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), f32),
                        (xs, Bm, Cm, dt, jnp.arange(S)))
    y = (y + p["D"][:, None] * xs).reshape(S, I)
    y = _rms(y * jax.nn.silu(z), p["norm"], arch["norm_eps"])
    return _mm(y, p["out_proj"]["kernel"], quant)


def _controls(quant):
    """(the projections' control, the state's controls) of one `quant`."""
    if quant in STATE_CONTROLS:
        return None, STATE_CONTROLS[quant]
    return quant, {}


def block(arch, kind, p, x, quant=None):
    quant, state = _controls(quant)
    r, eps = arch["residual_scale"], arch["norm_eps"]
    h = _rms(x, p["ln_attn"]["scale"], eps)
    if kind == ATTN:
        x = x + r * attention_mixer(arch, p["attn"], h, quant)
    else:
        x = x + r * mamba_mixer(arch, p["ssm"], h, quant, **state)
    h = _rms(x, p["ln_mlp"]["scale"], eps)
    return x + r * _swiglu(h, p["mlp"], quant)


def embed(arch, params, tokens):
    return (jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
            * arch["embed_scale"])


def head(arch, params, hidden, quant=None):
    quant, _ = _controls(quant)
    h = _rms(hidden, params["ln_f"]["scale"], arch["norm_eps"])
    table = params["embed" if arch["tied_head"] else "lm_head"]
    return (_mm(h, table.astype(jnp.float32).T, quant)
            / arch["logits_divisor"])


def logits(arch, params, tokens, quant=None):
    """tokens [S] -> [S, V]: the whole forward pass."""
    x = embed(arch, params, tokens)
    for i, kind in enumerate(arch["layer_kinds"]):
        x = block(arch, kind, params[f"block_{i}"], x, quant)
    return head(arch, params, x, quant)


@functools.lru_cache(maxsize=None)
def _jitted(what, arch_items, quant):
    arch = dict(arch_items)
    if what == "embed":
        return jax.jit(functools.partial(embed, arch))
    if what in (ATTN, SSM):
        return jax.jit(functools.partial(block, arch, what, quant=quant))
    if what == "head_rows":
        def rows_head(params, hid, start, n_rows):
            rows = jax.lax.dynamic_slice_in_dim(hid, start, n_rows, 0)
            return head(arch, params, rows, quant)
        return jax.jit(rows_head, static_argnames=("n_rows",))
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
    layer at a time, the head over the served rows alone. Lengths are
    padded to blocks so that a few shapes compile."""
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


# ---- required bytes and operations of one decode tick --------------------
def matmul_params(arch):
    """Parameters a decoded token multiplies: every layer's matrices
    and the head (tied: the embedding table, read once as the head; the
    lookup multiplies nothing)."""
    tree = dict(layout(arch))
    tree.pop("embed" if not arch["tied_head"] else "lm_head", None)
    return sum(math.prod(s) for s, kind in jax.tree.leaves(
        tree, is_leaf=_is_spec) if kind in ("matrix", "table"))


def _layers(arch, kind):
    return sum(k == kind for k in arch["layer_kinds"])


def state_bytes_per_lane(arch):
    """A lane's state and convolution tails (both float32) over the
    state-space layers: read and written once a tick each."""
    H, P, N, _, K, _, W = _ssm_widths(arch)
    return _layers(arch, SSM) * (H * P * N + (K - 1) * W) * 4


def kv_bytes_per_position(arch, kv_bytes=2):
    return (2 * _layers(arch, ATTN) * arch["num_kv_heads"]
            * arch["head_dim"] * kv_bytes)


def tick_bytes(arch, lanes_decoding, context_sum, weight_bytes=2):
    """Bytes one tick must move: every weight once, each decoding
    lane's state and tails read and written, the cached positions' K/V
    read and one position a lane written."""
    return (matmul_params(arch) * weight_bytes
            + 2 * lanes_decoding * state_bytes_per_lane(arch)
            + (context_sum + lanes_decoding)
            * kv_bytes_per_position(arch))


def tick_flops(arch, lanes_decoding, context_sum):
    """Flops one tick must do: 2 per parameter a decoding lane's row
    multiplies, the softmax layers' Q K^T and P V over the context, and
    the state's decay, rank-one update and read-out a state-space
    head."""
    H, P, N = _ssm_widths(arch)[:3]
    return (2 * matmul_params(arch) * lanes_decoding
            + 4 * _layers(arch, ATTN) * arch["num_heads"]
            * arch["head_dim"] * context_sum
            + 5 * _layers(arch, SSM) * H * P * N * lanes_decoding)


def _least(nbytes, flops, peaks):
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    t_f = flops / peaks["bf16_flops_per_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")


def tick_least_seconds(arch, peaks, *, lanes_decoding, context_sum):
    """(seconds, "bytes" | "flops"): the least time the chip could
    take for what the tick was asked to do."""
    return _least(tick_bytes(arch, lanes_decoding, context_sum),
                  tick_flops(arch, lanes_decoding, context_sum), peaks)


def ssm_step_bytes(arch, lanes_decoding):
    """Bytes ONE state-space layer's step must move: the decoding
    lanes' state read and written, decay, dt x in and y out over the
    inner width, B and C in."""
    H, P, N, G, _, I, _ = _ssm_widths(arch)
    return lanes_decoding * (2 * H * P * N + 3 * I + 2 * G * N) * 4


def ssm_step_least_seconds(arch, peaks, *, lanes_decoding):
    """(seconds, bound) of one state-space layer's S = 1 state step
    over the lanes that decode (lanes that ride the call and do not
    decode are not asked)."""
    H, P, N = _ssm_widths(arch)[:3]
    return _least(ssm_step_bytes(arch, lanes_decoding),
                  5 * H * P * N * lanes_decoding, peaks)
