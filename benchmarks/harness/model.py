"""From a configuration file's `arch` block to the program's model.

The one place that knows `TransformerLM`'s field names. The mapping is
the one `compat/hf.py` (`from_hf_gpt2`, `from_hf_qwen2`) uses.
"""


def program_model(arch, *, max_len=None, attn_impl=None, dtype=None):
    """`TransformerLM` for this `arch`. `max_len` is the positions the
    program holds (a learned table keeps the published size; with RoPE
    it is the cache a cell asks for)."""
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerLM

    if arch["hidden_size"] != arch["num_heads"] * arch["head_dim"]:
        raise ValueError("TransformerLM takes hidden = heads x head_dim")
    kw = dict(
        vocab_size=arch["vocab_size"], num_layers=arch["num_layers"],
        num_heads=arch["num_heads"], head_dim=arch["head_dim"],
        max_len=int(max_len or arch["max_positions"]),
        attn_bias=bool(arch["qkv_bias"]),
        attn_out_bias=bool(arch["out_bias"]),
        ln_eps=arch["norm_eps"], norm=arch["norm"],
        mlp_impl={"gelu_tanh": "gelu", "swiglu": "swiglu"}[arch["mlp"]],
        mlp_hidden=arch["mlp_hidden"], tied_head=arch["tied_head"],
        dtype=jnp.dtype(dtype or arch["compute_dtype"]))
    if arch["num_kv_heads"] != arch["num_heads"]:
        kw["num_kv_heads"] = arch["num_kv_heads"]
    if arch["positions"] == "rope":
        kw.update(pos_emb="rope", rope_theta=arch["rope_theta"])
    if attn_impl:
        kw["attn_impl"] = attn_impl
    return TransformerLM(**kw)
