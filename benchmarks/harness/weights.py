"""Weights from `--seed`, made by the benchmark on the device in one
jitted call.

The program and the plain reference are each handed a tree made here
(the reference makes its own copy from the same seed); neither takes
anything the other made. The layout is the parameter tree the program's
`TransformerLM` declares: `check_layout` compares names and shapes with
`jax.eval_shape(model.init)` and fails loudly where they part.

Values: matrices and tables normal(0, 0.02); norm scales 1 + normal(0,
0.02); every bias normal(0, 0.02), so that a dropped bias or scale
shows in `correct`.
"""

import functools
import math


def layout(arch, max_len):
    """Nested dict of (shape, kind); kind is 'matrix' | 'scale' |
    'bias'."""
    d, H, Hkv, D = (arch["hidden_size"], arch["num_heads"],
                    arch["num_kv_heads"], arch["head_dim"])
    m = arch["mlp_hidden"]

    def dense(i, o, bias):
        out = {"kernel": ((i, o), "matrix")}
        if bias:
            out["bias"] = ((o,), "bias")
        return out

    def norm():
        out = {"scale": ((d,), "scale")}
        if arch["norm"] == "layernorm":
            out["bias"] = ((d,), "bias")
        return out

    if arch["mlp"] == "swiglu":
        mlp = {"gate": dense(d, m, False), "up": dense(d, m, False),
               "down": dense(m, d, False)}
    else:
        mlp = {"wi": dense(d, m, arch["mlp_bias"]),
               "wo": dense(m, d, arch["mlp_bias"])}
    tree = {"embed": ((arch["vocab_size"], d), "matrix"),
            "ln_f": norm()}
    if arch["positions"] == "learned":
        tree["pos"] = ((max_len, d), "matrix")
    if not arch["tied_head"]:
        tree["lm_head"] = ((arch["vocab_size"], d), "matrix")
    for i in range(arch["num_layers"]):
        tree[f"block_{i}"] = {
            "attn": {"qkv": dense(d, (H + 2 * Hkv) * D,
                                  arch["qkv_bias"]),
                     "out": dense(H * D, d, arch["out_bias"])},
            "ln_attn": norm(), "ln_mlp": norm(), "mlp": mlp}
    return tree


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def seed_key(seed):
    """A PRNG key from any whole number up to a little over 2**31.
    The `rbg` generator: one hardware random-bits operation per call,
    so the program that makes the weights compiles in seconds."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31)


@functools.lru_cache(maxsize=None)
def _maker(arch_items, max_len, matrix_dtype):
    import jax
    import jax.numpy as jnp

    spec = layout(dict(arch_items), max_len)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_spec)
    matrix_dtype = jnp.dtype(matrix_dtype)
    # one random call per distinct (shape, kind): the layers' leaves
    # are rows of one stacked draw
    groups = {}
    for i, leaf in enumerate(leaves):
        groups.setdefault(leaf, []).append(i)

    def make(key):
        out = [None] * len(leaves)
        for g, ((shape, kind), idx) in enumerate(groups.items()):
            n = 0.02 * jax.random.normal(
                jax.random.fold_in(key, g), (len(idx), *shape),
                jnp.float32)
            if kind == "matrix":
                n = n.astype(matrix_dtype)
            elif kind == "scale":
                n = 1.0 + n
            for row, i in enumerate(idx):
                out[i] = n[row]
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)


def make_params(arch, max_len, seed, matrix_dtype):
    """The whole tree, on the default device, in one jitted call.
    `matrix_dtype` is the type matrices are kept in (f32 master
    weights for training, bf16 for serving); vectors stay f32."""
    return _maker(tuple(sorted(arch.items())), int(max_len),
                  str(matrix_dtype))(seed_key(seed))


def check_layout(arch, max_len, model):
    """Names and shapes of `layout` against what the program's model
    declares (shapes only: nothing is computed)."""
    import jax
    import jax.numpy as jnp
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


def count(arch, max_len):
    import jax
    return sum(math.prod(s) for s, _ in jax.tree.leaves(
        layout(arch, max_len), is_leaf=_is_spec))
