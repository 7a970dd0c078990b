"""The plain reference: a decoder-only transformer in straightforward
`jax.numpy`, float32, `jax.default_matmul_precision("highest")`; no
kernels, no cache, no batching tricks. It imports nothing of the
program and is handed weights the benchmark made (`weights.py`).

Follows the published descriptions (GPT-2: learned positions,
LayerNorm, tanh-GELU MLP, biases everywhere, tied head; Qwen2: RoPE in
the half-split layout, RMSNorm, SwiGLU, grouped-query attention, q/k/v
bias and no output bias, tied head). The fused q|k|v projection is one
[d, (H + 2 Hkv) D] matrix, heads major.

`quant` is the CONTROL of `correct`, not a reference: the same
arithmetic with every projection's two operands rounded to the step
below bf16 that a later PR would be tempted to take - "int8" (each
activation row and each weight column scaled by its own largest
magnitude) or "fp8" (e4m3, each operand scaled by its largest
magnitude). The rounded operands are multiplied exactly, as an int8 or
fp8 matrix unit with a wide accumulator would.
"""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    # straight-through: the backward pass sees the rounded operands
    return x + jax.lax.stop_gradient(q - x)


def _fake_fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0       # e4m3's largest finite
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, quant):
    """x [..., i] @ w [i, o] in float32 at highest precision."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "int8":
        x = _fake_int8(x, -1)
        w = _fake_int8(w, 0)
    elif quant == "fp8":
        x, w = _fake_fp8(x), _fake_fp8(w)
    elif quant is not None:
        raise ValueError(quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def _dense(x, p, quant):
    y = _mm(x, p["kernel"], quant)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y


def _norm(x, p, arch):
    eps = arch["norm_eps"]
    if arch["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
        return y * p["scale"] + p["bias"]
    ms = (x * x).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * p["scale"]


def _rope(x, theta):
    """x [B, S, H, D], positions 0..S-1, half-split (rotate_half)."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1)


def _attention(q, k, v):
    """Causal softmax attention; q [B,S,H,D], k/v [B,S,Hkv,D]."""
    B, S, H, D = q.shape
    g = H // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k, precision=HIGHEST)
    s = s * (D ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, v, precision=HIGHEST)


def _block(arch, p, x, quant):
    B, S, d = x.shape
    H, Hkv, D = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    h = _norm(x, p["ln_attn"], arch)
    qkv = _dense(h, p["attn"]["qkv"], quant)
    q = qkv[..., :H * D].reshape(B, S, H, D)
    k = qkv[..., H * D:(H + Hkv) * D].reshape(B, S, Hkv, D)
    v = qkv[..., (H + Hkv) * D:].reshape(B, S, Hkv, D)
    if arch["positions"] == "rope":
        q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
    o = _attention(q, k, v).reshape(B, S, H * D)
    x = x + _dense(o, p["attn"]["out"], quant)
    h = _norm(x, p["ln_mlp"], arch)
    if arch["mlp"] == "swiglu":
        m = p["mlp"]
        h = (jax.nn.silu(_dense(h, m["gate"], quant))
             * _dense(h, m["up"], quant))
        h = _dense(h, m["down"], quant)
    else:
        h = jax.nn.gelu(_dense(h, p["mlp"]["wi"], quant),
                        approximate=True)
        h = _dense(h, p["mlp"]["wo"], quant)
    return x + h


def embed(arch, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    if arch["positions"] == "learned":
        x = x + params["pos"][:tokens.shape[1]].astype(jnp.float32)
    return x


def head(arch, params, hidden, quant=None):
    """ln_f then the (tied) head: [.., d] -> [.., V] logits."""
    h = _norm(hidden, params["ln_f"], arch)
    table = params["embed" if arch["tied_head"] else "lm_head"]
    return _mm(h, table.astype(jnp.float32).T, quant)


def hidden_states(arch, params, tokens, quant=None, remat=False):
    """tokens [B, S] -> the last block's output [B, S, d]."""
    x = embed(arch, params, tokens)
    blk = functools.partial(_block, arch, quant=quant)
    if remat:
        blk = jax.checkpoint(blk)
    for i in range(arch["num_layers"]):
        x = blk(params[f"block_{i}"], x)
    return x


def logits(arch, params, tokens, quant=None):
    return head(arch, params, hidden_states(arch, params, tokens, quant),
                quant)


def lm_loss_sum(arch, params, tokens, quant=None):
    """Sum over the rows' S-1 next-token cross entropies (a sum, so
    that blocks of rows add up to the batch's mean)."""
    lg = head(arch, params,
              hidden_states(arch, params, tokens, quant, remat=True),
              quant)[:, :-1]
    lse = jax.nn.logsumexp(lg, axis=-1)
    hit = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    return (lse - hit).sum()


@functools.lru_cache(maxsize=None)
def _jitted(kind, arch_items, quant):
    """One jitted function per (what, arch, precision), so that a
    second call finds the first one's compiled program."""
    arch = dict(arch_items)
    if kind == "loss_and_grad":
        return jax.jit(jax.value_and_grad(
            functools.partial(lm_loss_sum, arch, quant=quant)))
    if kind == "embed":
        return jax.jit(functools.partial(embed, arch))
    if kind == "block":
        return jax.jit(functools.partial(_block, arch, quant=quant))
    if kind == "head_rows":
        def rows_head(params, hid, start, n_rows):
            rows = jax.lax.dynamic_slice_in_dim(hid[0], start, n_rows, 0)
            return head(arch, params, rows, quant)
        return jax.jit(rows_head, static_argnames=("n_rows",))
    raise ValueError(kind)


def _fn(kind, arch, quant=None):
    return _jitted(kind, tuple(sorted(arch.items())), quant)


def loss_and_grad(arch, params, tokens, quant=None, rows_per_block=4):
    """Mean next-token loss of the batch [B, S] and its gradient, in
    blocks of rows so that it fits beside nothing else on one chip."""
    fn = _fn("loss_and_grad", arch, quant)
    B, S = tokens.shape
    total, grads = None, None
    for r in range(0, B, rows_per_block):
        part = fn(params, tokens[r:r + rows_per_block])
        total, grads = (part if grads is None
                        else _tree_add((total, grads), part))
    return _tree_scale((total, grads), 1.0 / (B * (S - 1)))


_tree_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                    donate_argnums=(0,))
_tree_scale = jax.jit(lambda a, k: jax.tree.map(lambda x: x * k, a),
                      donate_argnums=(0,))
_tree_zeros = jax.jit(lambda a: jax.tree.map(jnp.zeros_like, a))


# ---- AdamW as optax.adamw(learning_rate) defines it -------------------
ADAMW = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4)


@functools.partial(jax.jit, static_argnames=("lr",), donate_argnums=(0, 2, 3))
def adamw_step(params, grads, mu, nu, count, lr):
    b1, b2, eps, wd = (ADAMW[k] for k in ("b1", "b2", "eps",
                                          "weight_decay"))
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count

    def upd(p, m, v):
        return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)

    return jax.tree.map(upd, params, mu, nu), mu, nu, count


@jax.jit
def leaf_norms(tree):
    """The L2 norm of every leaf, as one vector in tree order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def leaf_diff_norms(a, b):
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def train_reference(arch, make_params, batches, lr, quant=None):
    """Follow the first len(batches) steps from `make_params()`: per
    step the loss, the first gradient (kept on the host) with its
    per-leaf norms, and the per-leaf norms of the parameters' change
    after the last step (against a second `make_params()`, so that no
    copy of the start is held through the steps)."""
    import numpy as np
    params = make_params()
    mu, nu = _tree_zeros(params), _tree_zeros(params)
    count = jnp.zeros((), jnp.int32)
    losses, first_grad, first_norms = [], None, None
    for toks in batches:
        loss, grads = loss_and_grad(arch, params, jnp.asarray(toks),
                                    quant)
        if first_grad is None:
            first_norms = np.asarray(leaf_norms(grads))
            first_grad = jax.device_get(grads)
        losses.append(float(loss))
        params, mu, nu, count = adamw_step(params, grads, mu, nu, count,
                                           lr)
        del grads
    del mu, nu
    change = np.asarray(leaf_diff_norms(params, make_params()))
    return {"losses": losses, "grad_norms": first_norms,
            "first_grad": first_grad, "change_norms": change}


@jax.jit
def relative_difference(got, want):
    """|got - want| / |want| over whole trees (all leaves as one
    vector): first order in a rounding error, and steady from seed to
    seed where a worst leaf swings."""
    num = sum(jnp.sum(jnp.square(a.astype(jnp.float32) - b))
              for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    den = sum(jnp.sum(jnp.square(b)) for b in jax.tree.leaves(want))
    return jnp.sqrt(num / den)


# ---- serving: the gap of a served token below the reference's best ----
def _pad_to(n, block):
    return -(-n // block) * block


def served_logits(arch, params, prompt, served, quant=None,
                  seq_block=512, row_block=256):
    """Logits [len(served), V] of the reference at each position whose
    next token the system served: one full forward over prompt ++
    served (teacher-forced; causal, so the padding after the end
    reaches nothing), layer by layer. Lengths are padded to blocks so
    that a few shapes compile."""
    import numpy as np
    P, n = len(prompt), len(served)
    n_rows = _pad_to(n, row_block)
    seq = np.zeros((1, _pad_to(P - 1 + n_rows, seq_block)), np.int32)
    seq[0, :P] = prompt
    seq[0, P:P + n - 1] = served[:n - 1]
    x = _fn("embed", arch)(params, jnp.asarray(seq))
    blk = _fn("block", arch, quant)
    for i in range(arch["num_layers"]):
        x = blk(params[f"block_{i}"], x)
    out = _fn("head_rows", arch, quant)(params, x, P - 1, n_rows=n_rows)
    return out[:n]


def token_gaps(ref_logits, tokens):
    """Per position: how far the token's logit lies below the
    reference's best (0 where it is the reference's argmax)."""
    import numpy as np
    lg = np.asarray(ref_logits, np.float32)
    best = lg.max(-1)
    return best - lg[np.arange(len(tokens)), np.asarray(tokens)]
